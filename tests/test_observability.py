"""Unified telemetry: registry semantics, spans, exporters, integration.

The registry is process-global (native-tier cells are keyed by series
name in the cross-thread stat store), so tests use per-test metric names
or fresh MetricsRegistry instances plus delta assertions — never absolute
values of shared series.
"""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (attach_context, capture_context,
                                      load_jsonl, render_prometheus, span,
                                      span_path, write_jsonl)
from paddle_tpu.observability.metrics import (MetricsRegistry, get_registry)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_labels_and_monotonicity():
    reg = MetricsRegistry()
    fam = reg.counter("obs_t1_reqs", "x", labelnames=("engine",))
    fam.labels(engine="dense").inc()
    fam.labels(engine="dense").inc(4)
    fam.labels(engine="paged").inc(2)
    assert fam.labels(engine="dense").value == 5
    assert fam.labels(engine="paged").value == 2
    with pytest.raises(ValueError):
        fam.labels(engine="dense").inc(-1)
    with pytest.raises(ValueError):
        fam.labels(wrong="dense")


def test_registration_is_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    a = reg.counter("obs_t2_c", "x")
    assert reg.counter("obs_t2_c") is a
    with pytest.raises(ValueError):
        reg.gauge("obs_t2_c")
    reg.counter("obs_t2_lab", labelnames=("a",))
    with pytest.raises(ValueError):
        reg.counter("obs_t2_lab", labelnames=("b",))


def test_gauge_tracks_peak():
    reg = MetricsRegistry()
    g = reg.gauge("obs_t3_depth", "x")
    g.set(3)
    g.set(9)
    g.set(2)
    assert g.value == 2
    assert g.peak == 9


def test_histogram_buckets_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("obs_t4_lat", "x", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(56.05)
    assert h.bucket_counts() == [1, 2, 1, 1]   # last = +Inf overflow
    # exact below the reservoir cap: quantiles come from the sorted sample
    assert h.quantile(0.5) == 0.5
    assert h.quantile(0.99) == 50.0


def test_histogram_quantile_edge_cases():
    reg = MetricsRegistry()
    h = reg.histogram("obs_t4b_edge", "x", buckets=(0.1, 1.0))
    # empty: no estimate, not a crash
    assert h.quantile(0.5) is None
    assert h.quantile(0.0) is None and h.quantile(1.0) is None
    # singleton: every quantile is the one sample
    h.observe(0.7)
    assert h.quantile(0.0) == h.quantile(0.5) == h.quantile(1.0) == 0.7
    # extremes are the EXACT tracked min/max, not reservoir artifacts
    for v in (0.2, 3.0, 0.05, 1.5):
        h.observe(v)
    assert h.quantile(0.0) == 0.05
    assert h.quantile(1.0) == 3.0
    # out-of-range q raises instead of silently clamping
    with pytest.raises(ValueError):
        h.quantile(-0.01)
    with pytest.raises(ValueError):
        h.quantile(1.01)


def test_histogram_quantile_extremes_survive_reservoir_eviction():
    from paddle_tpu.observability.metrics import _RESERVOIR_CAP
    reg = MetricsRegistry()
    h = reg.histogram("obs_t4c_extremes", "x", buckets=(0.5,))
    h.observe(-123.0)                     # global min, observed FIRST
    for i in range(_RESERVOIR_CAP * 4):   # likely evicts the early sample
        h.observe(float(i % 100))
    h.observe(9999.0)                     # global max
    assert h.quantile(0.0) == -123.0
    assert h.quantile(1.0) == 9999.0


def test_histogram_quantile_sane_past_reservoir_cap():
    from paddle_tpu.observability.metrics import _RESERVOIR_CAP
    reg = MetricsRegistry()
    h = reg.histogram("obs_t5_big", "x", buckets=(0.5,))
    n = _RESERVOIR_CAP * 4
    for i in range(n):
        h.observe(i / n)   # uniform on [0, 1)
    assert h.count == n
    q50 = h.quantile(0.5)
    assert 0.3 < q50 < 0.7  # unbiased estimate of the true 0.5


def test_thread_safety_counter():
    reg = MetricsRegistry()
    c = reg.counter("obs_t6_mt", "x")

    def burst():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=burst) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 8000


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_builds_path():
    assert span_path() == ""
    with span("outer"):
        assert span_path() == "outer"
        with span("inner") as s:
            assert span_path() == "outer/inner"
            assert s.path == "outer/inner"
        assert span_path() == "outer"
    assert span_path() == ""


def test_span_durations_reach_registry():
    hist = get_registry().get("span_duration_seconds")
    with span("obs_t7_marker"):
        time.sleep(0.01)
    child = hist.labels(span="obs_t7_marker")
    assert child.count >= 1
    assert child.sum >= 0.009


def test_span_context_propagates_across_threads():
    seen = {}

    def worker(token):
        with attach_context(token):
            with span("stage"):
                seen["path"] = span_path()
        seen["after"] = span_path()

    with span("producer"):
        t = threading.Thread(target=worker, args=(capture_context(),))
        t.start()
        t.join()
    assert seen["path"] == "producer/stage"
    assert seen["after"] == ""


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _sample_registry():
    reg = MetricsRegistry()
    reg.counter("obs_exp_reqs", "reqs", labelnames=("engine",)) \
        .labels(engine="dense").inc(7)
    g = reg.gauge("obs_exp_depth", "depth")
    g.set(4)
    g.set(1)
    h = reg.histogram("obs_exp_lat", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    return reg


def test_prometheus_rendering():
    text = render_prometheus(registry=_sample_registry())
    assert '# TYPE obs_exp_reqs counter' in text
    assert 'obs_exp_reqs{engine="dense"} 7' in text
    assert 'obs_exp_depth 1' in text
    assert 'obs_exp_depth_peak 4' in text
    # cumulative buckets + +Inf + sum/count
    assert 'obs_exp_lat_bucket{le="0.1"} 1' in text
    assert 'obs_exp_lat_bucket{le="1"} 2' in text
    assert 'obs_exp_lat_bucket{le="+Inf"} 3' in text
    assert 'obs_exp_lat_count 3' in text
    assert 'obs_exp_lat_quantile{quantile="0.5"} 0.5' in text


def test_jsonl_round_trip(tmp_path):
    reg = _sample_registry()
    path = str(tmp_path / "snap.jsonl")
    write_jsonl(path, registry=reg, series=reg.snapshot(
        include_native=False))
    series = load_jsonl(path)
    # re-rendered snapshot is value-identical to the live render
    assert render_prometheus(series=series) == render_prometheus(
        series=reg.snapshot(include_native=False))
    with open(path) as f:
        meta = json.loads(f.readline())
    assert meta["__meta__"]["format"] == "paddle_tpu.observability/1"
    assert meta["__meta__"]["series"] == len(series)


def test_jsonl_rejects_corrupt_lines(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"name": "ok", "type": "counter", "value": 1}\n'
                    '{"name": "trunc', encoding="utf-8")
    with pytest.raises(json.JSONDecodeError):
        load_jsonl(str(path))


def test_exporter_overhead_under_one_percent():
    """bench guard: rendering a snapshot must cost <1% of a tight 100k
    counter-inc loop — exporting may never be the hot path."""
    reg = MetricsRegistry()
    c = reg.counter("obs_overhead_c", "x")

    def ratio(n=100_000, renders=20):
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
        t1 = time.perf_counter()
        for _ in range(renders):
            render_prometheus(registry=reg)
        t2 = time.perf_counter()
        return (t2 - t1) / renders / (t1 - t0)

    # loop and renders are timed back to back, so a busy machine slows
    # both. One render is 10 to 100 us, less than a time slice and, under
    # five other workers, mostly its cache misses: a render is the mean of
    # twenty, and the ratio the median of seven
    ratios = sorted(ratio() for _ in range(7))
    assert ratios[3] < 0.01, ratios


# ---------------------------------------------------------------------------
# monitor shim — one store per process
# ---------------------------------------------------------------------------

def test_monitor_shim_shares_registry_store():
    from paddle_tpu.utils import monitor
    monitor.stat_reset("obs_shim_g")
    assert monitor.stat_update("obs_shim_g", 5) == 5
    assert monitor.stat_update("obs_shim_g", -2) == 3
    assert monitor.stat_peak("obs_shim_g") == 5
    # the registry snapshot sees the same cell (no shadow store)
    series = {s["name"]: s for s in get_registry().snapshot()}
    assert series["obs_shim_g"]["value"] == 3.0
    assert monitor.get_monitor_values()["obs_shim_g"] == 3
    monitor.stat_reset("obs_shim_g")
    assert monitor.stat_get("obs_shim_g") == 0


# ---------------------------------------------------------------------------
# profiler export filename collision fix
# ---------------------------------------------------------------------------

def test_chrome_export_handlers_never_collide(tmp_path):
    from paddle_tpu import profiler

    d = str(tmp_path)
    for _ in range(2):   # two handlers, same worker name, same second
        p = profiler.Profiler(
            on_trace_ready=profiler.export_chrome_tracing(d, "w"))
        p.start()
        with profiler.RecordEvent("e"):
            pass
        p.stop()
    traces = list(tmp_path.glob("w_time_*.paddle_trace.json"))
    assert len(traces) == 2, [t.name for t in traces]


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

def test_continuous_batcher_populates_serving_metrics():
    from paddle_tpu.inference.serving import ContinuousBatcher
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM

    reg = get_registry()

    def dense(name):
        return reg.get(name).labels(engine="dense")

    before_reqs = dense("serving_requests_total").value \
        if reg.get("serving_requests_total") else 0
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=32, num_hidden_layers=1,
                     num_attention_heads=2, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(3)
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        rids = [b.submit(rng.randint(0, 128, (5,)), 4) for _ in range(3)]
        outs = b.run_until_done()
    assert set(outs) == set(rids)

    assert dense("serving_requests_total").value == before_reqs + 3
    # all drained: depth gauge back to zero, but its peak saw the queue
    assert dense("serving_queue_depth").value == 0
    assert dense("serving_queue_depth").peak >= 1
    ttft = dense("serving_ttft_seconds")
    assert ttft.count >= 3
    assert sum(ttft.bucket_counts()) == ttft.count
    assert dense("serving_tokens_total").value >= 12
    # the local stats() contract survived the refactor
    s = b.stats()
    assert s["completed_requests"] == 3
    assert s["generated_tokens"] == 12
    assert s["pending_now"] == 0 and s["active_now"] == 0
    b.reset_stats()
    assert b.stats()["completed_requests"] == 0
    # per-instance reset must NOT clear the process-wide cumulative series
    assert dense("serving_requests_total").value == before_reqs + 3


def test_prometheus_dump_after_serving_has_populated_families():
    from paddle_tpu.inference.serving import _ServingStats
    _ServingStats("dense")   # idempotent: children are shared by series key
    text = render_prometheus()
    assert "# TYPE serving_requests_total counter" in text
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert "# TYPE serving_queue_depth gauge" in text


# ---------------------------------------------------------------------------
# spans in the JAX profiler's trace; executables and scopes by name
# ---------------------------------------------------------------------------

# every span of the serving path, by where it opens (PERF.md section 3)
GATEWAY_SPANS = ("gateway.dispatch", "gateway.replica_step", "gateway.poll")
SERVING_SPANS = ("serving.admit", "serving.prefill_chunk", "serving.grow",
                 "serving.sync_tables", "serving.launch", "serving.fetch",
                 "serving.pick")
TRAIN_SPANS = ("trainstep.assemble", "trainstep.launch",
               "trainstep.writeback")


def _tiny_llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    m.eval()
    return m


def _tiny_gateway():
    from paddle_tpu.inference.gateway import Gateway
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    batcher = PagedContinuousBatcher(
        _tiny_llama(), max_batch=2, s_max=64, block_size=8, n_pages=32,
        prefill_chunk=8, policy="ondemand", prefix_cache=True, compile=True)
    gateway = Gateway()
    gateway.add_replica("r0", batcher)
    return gateway, batcher


def _tiny_train_step(label):
    from paddle_tpu import jit, nn, optimizer
    paddle.seed(0)
    net = nn.Linear(8, 4)
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = jit.TrainStep(lambda x, y: ((net(x) - y) ** 2).mean(), opt,
                         opprof_label=label)
    return step, (paddle.ones([2, 8]), paddle.zeros([2, 4]))


def _host_events(trace_dir):
    """{name: [(start_ns, end_ns, stats)]} of the program's spans in the
    host planes."""
    import glob
    import os

    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("obs_", "gateway.", "serving.",
                                      "trainstep.")):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler window for the module: nested spans with tags, a few
    gateway steps over a tiny paged server, two compiled train steps."""
    import jax
    gateway, batcher = _tiny_gateway()
    rng = np.random.RandomState(5)
    step, batch = _tiny_train_step("obs.train_step")
    step(*batch)                       # the eager discovery pass
    step(*batch)                       # compiles
    with paddle.no_grad():
        gateway.submit(rng.randint(0, 128, (20,)), 3)    # warm both
        gateway.run_until_done()                         # executables
    batcher.reset_stats()
    admitted = get_registry().get("serving_admissions_total").labels(
        engine="paged")
    admitted_before = admitted.value
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        with span("obs_outer", rid=7, prompt_tokens=12):
            time.sleep(0.002)
            with span("obs_inner", replica="r0"):
                time.sleep(0.002)
            time.sleep(0.002)
        with paddle.no_grad():
            for n in (20, 9, 13):
                gateway.submit(rng.randint(0, 128, (n,)), 4)
            gateway.run_until_done()
        step(*batch)
        step(*batch)
    finally:
        jax.profiler.stop_trace()
    stats = dict(batcher.stats(),
                 admissions=admitted.value - admitted_before)
    batcher.close()
    return {"events": _host_events(trace_dir), "stats": stats}


def test_span_lands_in_the_profilers_host_plane_with_its_tags(traced):
    ev = traced["events"]
    (outer,) = ev["obs_outer"]
    (inner,) = ev["obs_inner"]
    assert outer[2] == {"rid": 7, "prompt_tokens": 12}
    assert inner[2] == {"replica": "r0"}
    # same clock, so nesting is containment, with room on both sides
    assert outer[0] < inner[0] < inner[1] < outer[1]
    assert inner[1] - inner[0] >= 1.5e6
    assert (inner[0] - outer[0]) >= 1.5e6 and (outer[1] - inner[1]) >= 1.5e6


def test_span_without_a_profiler_session_still_feeds_the_histogram():
    hist = get_registry().get("span_duration_seconds")
    child = hist.labels(span="obs_no_session")
    before = child.count
    with span("obs_no_session", rid=1):
        pass
    with span("obs_no_session"):
        pass
    assert child.count == before + 2


def test_serving_phase_spans_counted_as_the_batcher_counts(traced):
    ev, st = traced["events"], traced["stats"]
    for name in GATEWAY_SPANS + SERVING_SPANS:
        assert ev.get(name), f"no {name} span in the trace"
    assert st["admissions"] == 3 and st["steps"] > 0
    assert len(ev["serving.admit"]) == st["admissions"]
    assert len(ev["serving.launch"]) == st["steps"]
    assert len(ev["serving.sync_tables"]) == st["steps"]
    assert len(ev["serving.grow"]) == st["steps"]
    assert len(ev["serving.pick"]) == st["steps"]
    # a fetch per decode step and one for each admission's first token
    assert len(ev["serving.fetch"]) == st["steps"] + st["admissions"]
    # prompts of 20, 9 and 13 tokens in chunks of 8: 3 + 2 + 2
    assert len(ev["serving.prefill_chunk"]) == 7
    admit = sorted(ev["serving.admit"])
    assert [a[2]["prompt_tokens"] for a in admit] == [20, 9, 13]
    assert all(a[2]["hit_tokens"] == 0 and "rid" in a[2] for a in admit)
    assert len(ev["gateway.replica_step"]) == len(ev["gateway.poll"])
    assert {e[2]["replica"] for e in ev["gateway.replica_step"]} == {"r0"}


def test_serving_spans_nest_under_the_replica_step(traced):
    ev = traced["events"]
    steps = ev["gateway.replica_step"]

    def inside(child, parents):
        return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)

    for name in SERVING_SPANS:
        assert all(inside(e, steps) for e in ev[name]), name
    assert all(inside(c, ev["serving.admit"])
               for c in ev["serving.prefill_chunk"])
    # disjoint phases of one step: nothing of the poll or the dispatch
    # lies inside a replica step
    assert not any(inside(e, steps)
                   for e in ev["gateway.poll"] + ev["gateway.dispatch"])


def test_train_step_spans_in_order(traced):
    ev = traced["events"]
    for name in TRAIN_SPANS:
        assert len(ev[name]) == 2, name
    for a, l, w in zip(*(sorted(ev[n]) for n in TRAIN_SPANS)):
        assert a[1] <= l[0] and l[1] <= w[0]


@pytest.fixture
def hlo_texts(monkeypatch):
    """{label: optimized HLO text} of every executable that reaches its
    warm transition while the fixture is live, read where opprof reads it."""
    from paddle_tpu.observability import opprof
    texts = {}
    real = opprof.profile_compiled

    def keep(compiled, label=""):
        texts[label] = compiled.as_text()
        return real(compiled, label=label)

    monkeypatch.setattr(opprof, "profile_compiled", keep)
    was = opprof.enabled()
    opprof.enable()
    yield texts
    if not was:
        opprof.disable()


def test_executables_lower_under_their_label(hlo_texts):
    from paddle_tpu import jit

    def double(x):
        return x * 2

    plain = jit.to_static(double)
    labelled = jit.to_static(double)
    labelled._opprof_label = "obs.double_it"
    x = paddle.ones([4])
    for fn in (plain, labelled):
        fn(x)          # eager discovery
        fn(x)          # compiled: the warm transition
    step, batch = _tiny_train_step("obs.train_step")
    step(*batch)
    step(*batch)
    for label, module in (("static.double", "jit_static_double"),
                          ("obs.double_it", "jit_obs_double_it"),
                          ("obs.train_step", "jit_obs_train_step")):
        assert hlo_texts[label].startswith(f"HloModule {module},"), \
            hlo_texts[label][:120]


def test_serving_hlo_carries_the_models_scopes(hlo_texts):
    import re
    gateway, batcher = _tiny_gateway()
    with paddle.no_grad():
        gateway.submit(np.arange(20) % 128, 3)
        gateway.run_until_done()
    batcher.close()
    for label, module in (
            ("serving.paged_decode", "jit_serving_paged_decode"),
            ("serving.paged_prefill_chunk",
             "jit_serving_paged_prefill_chunk")):
        text = hlo_texts[label]
        assert text.startswith(f"HloModule {module},")
        paths = set(re.findall(r'op_name="([^"]+)"', text))
        scopes = {part for p in paths for part in p.split("/")}
        assert {"embed", "attn_norm", "qkv_rope", "paged_attention",
                "kv_scatter", "kv_gather", "scores", "o_proj", "mlp_norm",
                "mlp", "head"} <= scopes, sorted(scopes)
        # the three phases of the attention lie inside its scope
        for inner in ("kv_scatter", "kv_gather", "scores"):
            assert any(f"/paged_attention/{inner}/" in p for p in paths)
