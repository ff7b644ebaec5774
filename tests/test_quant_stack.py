"""Serving-path quantization stack (round 22): int8 KV pages, quantized
tier blobs, and int8 serving weights, wired end-to-end.

Five layers, <60s total:

  * observers + convert — the all-zero-first-batch HistObserver
    regression (degenerate [0, 1e-8] edges must re-initialize on the
    first nonzero batch), PTQ ``convert()`` round-trip error bounds
    across shapes/seeds, per-channel at least as tight as per-tensor,
    and the ``QuantedConv2D`` swap-walk reaching nested sublayers;
  * serving_quantize — quality bound on the sharpened tiny GPT (the
    40-step data-seed-0 recipe: greedy token-match >= 0.99, end-to-end
    logit MAE <= 0.05 — measured ~0.005), the per-layer fp fallback
    tripping on a planted per-tensor outlier (and NOT tripping
    channelwise), mesh ``serving_weight_spec`` placement staying
    numerically inert, and the ``quant.*`` counters;
  * kv_quant — constructor guards (whitelist, calibration prerequisite,
    the cache_quant/draft_model exclusions), int8 page pools decoding
    within the match bound vs fp, and the ``serving.kv_quant_*`` gauges;
  * tier_quant — demoted chains stored as int8+scale blobs at ~1/4 the
    raw bytes (spill counters), promotion dequantizing on install
    (``quant.dequant_seconds`` observed), hit parity and generated-token
    agreement with the fp-tier run, zero-leak ``audit_pages`` +
    ``audit_tiers``, the calibration digest in ``model_identity``, and
    the pause -> quantized demotion -> corrupt-blob -> resume drill
    degrading to an audited, token-exact full prefill;
  * tooling — the ``quant:`` bench_guard lane gating BOTH the decode
    tokens/s headline and the synthesized token-match series,
    ``telemetry_dump --prefix-stats`` spill columns (legacy line
    unchanged when the counters are absent), and the ledger's
    ``dequant`` waste row.
"""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.inference.session_store import model_identity
from paddle_tpu.quantization import (PTQ, AbsmaxObserver,
                                     ChannelAbsmaxObserver,
                                     FakeQuanterWithAbsMaxObserver,
                                     HistObserver, QAT, QuantConfig,
                                     QuantedConv2D, QuantedLinear,
                                     serving_quantize)

from greedy_ref import greedy_ref

pytestmark = pytest.mark.quant

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 16


def _np(t):
    return np.asarray(t._data)


@pytest.fixture(scope="module")
def sharp_lm():
    """Briefly trained tiny GPT: random-init argmax near-ties flip under
    any perturbation and would measure the MODEL, not the quantizer —
    40 AdamW steps on a fixed seed-0 batch sharpen the logits enough
    that the int8 stack's greedy decode matches fp exactly (the recipe
    the bench's weights arm uses)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    rng = np.random.RandomState(0)
    data = paddle.to_tensor(rng.randint(0, 128, (4, 33)))
    opt = optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())
    for _ in range(40):
        logits = m(data[:, :-1])
        loss = F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               data[:, 1:].reshape([-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _no_stale_calibration(sharp_lm):
    """kv_quant tests calibrate the shared model; everything else (and
    every tier_quant constructor) requires scales to be absent."""
    sharp_lm.calibrate_cachekv_int8(None)
    yield
    sharp_lm.calibrate_cachekv_int8(None)


_ref = greedy_ref


def _counter(name):
    from paddle_tpu.observability.metrics import get_registry
    return sum(s.get("value", 0) for s in get_registry().snapshot()
               if s.get("name") == name)


def _gauge(name):
    from paddle_tpu.observability.metrics import get_registry
    for s in get_registry().snapshot():
        if s.get("name") == name and s.get("type") == "gauge":
            return s.get("value")
    return None


def _hist_count(name):
    from paddle_tpu.observability.metrics import get_registry
    return sum(s.get("count", 0) for s in get_registry().snapshot()
               if s.get("name") == name)


# -- observers + convert ------------------------------------------------------

def test_hist_observer_survives_all_zero_first_batch():
    data = np.random.RandomState(0).randn(4096).astype(np.float32)
    ref = HistObserver(bins_count=256)
    ref.observe(data)
    # regression: a zeros-only first batch used to pin the edges to
    # [0, 1e-8]; every later re-bin collapsed the accumulated mass into
    # bin 0 and scales() returned ~1e-8 no matter the real data
    obs = HistObserver(bins_count=256)
    obs.observe(np.zeros(512, np.float32))
    obs.observe(data)
    assert float(obs.scales()) > 0.1
    assert float(obs.scales()) == pytest.approx(float(ref.scales()),
                                                rel=0.05)
    # zeros-only stays at the defined fallback scale
    z = HistObserver(bins_count=256)
    z.observe(np.zeros(64, np.float32))
    assert float(z.scales()) == 1.0


@pytest.mark.parametrize("seed,shape", [(0, (8, 16)), (1, (16, 64)),
                                        (2, (7, 33))])
def test_ptq_convert_roundtrip_error_bound(seed, shape):
    rng = np.random.RandomState(seed)
    lin = nn.Linear(*shape)
    net = nn.Sequential(lin)
    ptq = PTQ(QuantConfig(activation=AbsmaxObserver(),
                          weight=AbsmaxObserver()))
    pnet = ptq.quantize(net)
    x = paddle.to_tensor(rng.randn(32, shape[0]).astype(np.float32))
    ref = _np(net(x))
    inet = ptq.convert(pnet)
    out = _np(inet(x))
    # absmax int8: per-element weight error <= scale/254; the matmul
    # accumulates ~in_features of them — bound the output rel error
    denom = max(float(np.abs(ref).max()), 1e-6)
    assert float(np.abs(out - ref).max()) / denom < 0.05
    # per-output-channel scales can only tighten the reconstruction
    cptq = PTQ(QuantConfig(activation=AbsmaxObserver(),
                           weight=ChannelAbsmaxObserver()))
    cnet = cptq.convert(cptq.quantize(net))
    w = _np(lin.weight)
    for layers in (inet, cnet):
        conv = [l for l in layers.sublayers()
                if type(l).__name__ == "_ConvertedLinear"][0]
        sc = (_np(conv.scale) if not isinstance(conv.scale, float)
              else conv.scale)
        werr = np.abs(_np(conv.w_int8).astype(np.float32)
                      * (sc / conv._qmax) - w).max()
        if layers is inet:
            per_tensor_err = werr
    assert werr <= per_tensor_err + 1e-7


def test_quanted_conv2d_swap_walk_reaches_nested_layers():
    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.conv = nn.Conv2D(2, 4, 3, padding=1)
            self.act = nn.ReLU()

        def forward(self, x):
            return self.act(self.conv(x))

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.stem = nn.Conv2D(1, 2, 3, padding=1)
            self.block = Block()
            self.head = nn.Linear(4 * 8 * 8, 5)

        def forward(self, x):
            h = self.block(self.stem(x))
            return self.head(h.reshape([x.shape[0], -1]))

    net = Net()
    cfg = QuantConfig(activation=FakeQuanterWithAbsMaxObserver(),
                      weight=FakeQuanterWithAbsMaxObserver())
    qnet = QAT(cfg).quantize(net)
    kinds = [type(l).__name__ for l in qnet.sublayers()]
    assert kinds.count("QuantedConv2D") == 2     # stem AND nested block
    assert kinds.count("QuantedLinear") == 1
    assert isinstance(qnet.block.conv, QuantedConv2D)
    assert isinstance(qnet.head, QuantedLinear)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(2, 1, 8, 8).astype(np.float32))
    assert tuple(qnet(x).shape) == (2, 5)
    # the original model is untouched by the walk
    assert not any(isinstance(l, (QuantedConv2D, QuantedLinear))
                   for l in net.sublayers())


# -- serving_quantize ---------------------------------------------------------

def test_serving_quantize_quality_bound_and_report(sharp_lm):
    before_q = _counter("quant.layers_quantized")
    before_f = _counter("quant.layers_fallback")
    q = serving_quantize(sharp_lm)
    rep = q._serving_quant_report
    assert rep["layers_quantized"] >= 1 and rep["bytes_saved"] > 0
    assert rep["err_bound"] == pytest.approx(0.02)
    assert _counter("quant.layers_quantized") - before_q == \
        rep["layers_quantized"]
    assert _counter("quant.layers_fallback") - before_f == \
        rep["layers_fallback"]
    # documented quality bound: logit MAE <= 0.05 (measured ~0.005 on
    # this recipe) and greedy token-match >= 0.99 vs the fp model
    x = paddle.to_tensor(np.random.RandomState(5).randint(0, 128, (4, 24)))
    with paddle.no_grad():
        mae = float(np.abs(_np(sharp_lm(x)) - _np(q(x))).mean())
    assert mae <= 0.05, mae
    match = []
    with paddle.no_grad():
        for s in range(3):
            p = np.random.RandomState(100 + s).randint(0, 128, (20,))
            match.append(np.mean(_ref(sharp_lm, p, 10)[20:]
                                 == _ref(q, p, 10)[20:]))
    assert float(np.mean(match)) >= 0.99, match


def test_serving_quantize_fallback_trips_on_planted_outlier():
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    w = _np(net[0].weight).copy()
    w[:, 0] *= 200.0          # one huge column starves per-tensor scales
    net[0].weight.set_value(paddle.to_tensor(w.astype(np.float32)))
    per_tensor = serving_quantize(net, channelwise=False)
    rep = per_tensor._serving_quant_report
    assert rep["layers_fallback"] >= 1
    assert rep["layers"]["0"]["quantized"] is False
    # per-channel scales isolate the outlier column: same layer passes
    chan = serving_quantize(net, channelwise=True)
    crep = chan._serving_quant_report
    assert crep["layers"]["0"]["quantized"] is True
    assert crep["layers"]["0"]["rel_err"] < rep["layers"]["0"]["rel_err"]


def test_serving_quantize_mesh_placement_is_numerically_inert():
    from paddle_tpu.distributed.mesh import MeshRuntime
    paddle.seed(9)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    rt = MeshRuntime({"tensor": 2})
    assert rt.serving_weight_spec((16, 32)) == (None, "tensor")
    plain = serving_quantize(net)
    placed = serving_quantize(net, mesh=rt)
    assert placed._serving_quant_report["layers_quantized"] == \
        plain._serving_quant_report["layers_quantized"]
    x = paddle.to_tensor(np.random.RandomState(3)
                         .randn(4, 16).astype(np.float32))
    with paddle.no_grad():
        np.testing.assert_allclose(_np(plain(x)), _np(placed(x)),
                                   rtol=1e-5, atol=1e-6)


# -- kv_quant: int8 KV pages --------------------------------------------------

def test_kv_quant_int8_pages_match_fp_within_bound(sharp_lm):
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, (20,)).astype(np.int64)
               for _ in range(3)]

    def run(**kw):
        bt = PagedContinuousBatcher(sharp_lm, max_batch=2, s_max=64,
                                    block_size=BLOCK, compile=False, **kw)
        try:
            with paddle.no_grad():
                rids = [bt.submit(p, 6) for p in prompts]
                res = bt.run_until_done(max_steps=60000)
            pool_dtype = str(bt._state["layers"][0][0].dtype)
            bt.audit_pages()
            return [res[r] for r in rids], pool_dtype
        finally:
            bt.close()

    fp_outs, fp_dtype = run()
    assert "int8" not in fp_dtype
    sharp_lm.calibrate_cachekv_int8(
        np.random.RandomState(0).randint(0, 128, (2, 32)))
    q_outs, q_dtype = run(kv_quant="int8")
    assert "int8" in q_dtype
    assert _gauge("serving.kv_quant_enabled") == 1
    assert _gauge("serving.kv_quant_bytes_saved") > 0
    match = float(np.mean([np.mean(a[20:] == b[20:])
                           for a, b in zip(fp_outs, q_outs)]))
    assert match >= 0.99, match


# -- tier_quant: int8 demotion blobs ------------------------------------------

def _tiered(lm, tmp, host_bytes, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("s_max", 96)
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("n_pages", 12)
    kw.setdefault("compile", False)
    kw.setdefault("policy", "ondemand")
    kw.setdefault("prefix_cache", True)
    kw.setdefault("host_kv_gib", host_bytes / 2**30)
    return PagedContinuousBatcher(lm, **kw)


def _churn(bt, seed=3, n=8, length=51):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        bt.submit(rng.randint(0, 128, (length,)).astype(np.int64), 4)
    bt.run_until_done(max_steps=60000)


def test_tier_quant_spill_capacity_promote_and_audits(sharp_lm, tmp_path):
    rng = np.random.RandomState(17)
    prefixes = [rng.randint(0, 128, (3 * BLOCK,)).astype(np.int64)
                for _ in range(4)]
    prompts = [np.concatenate([prefixes[i % 4],
                               rng.randint(0, 128, (5,))]).astype(np.int64)
               for i in range(8)]

    def run(tier_quant):
        raw0 = _counter("serving.prefix_spill_raw_bytes")
        blob0 = _counter("serving.prefix_spill_blob_bytes")
        bt = _tiered(sharp_lm, tmp_path, host_bytes=6 * 16384,
                     tier_quant=tier_quant)
        try:
            with paddle.no_grad():
                for p in prefixes:
                    bt.submit(p, 4)
                bt.run_until_done(max_steps=60000)
                rids = [bt.submit(p, 4) for p in prompts]
                res = bt.run_until_done(max_steps=60000)
            st = bt.prefix_cache.stats()
            bt.audit_pages()                     # raises on any leak
            rep = bt.prefix_cache.audit_tiers()  # raises on byte drift
            return {
                "outs": [res[r] for r in rids],
                "raw": _counter("serving.prefix_spill_raw_bytes") - raw0,
                "blob": _counter("serving.prefix_spill_blob_bytes")
                        - blob0,
                "promotions": st["promotions"],
                "failures": st["promotion_failures"],
                "host_bytes": rep.get("host_bytes", 0),
            }
        finally:
            bt.close()

    fp = run(None)
    dq0 = _hist_count("quant.dequant_seconds")
    q = run("int8")
    assert fp["raw"] == fp["blob"]               # fp blobs spill as-is
    assert q["raw"] > 0 and q["blob"] > 0
    assert q["raw"] / q["blob"] >= 3.5           # int8 codes + scales
    assert q["promotions"] > 0 and q["failures"] == 0
    assert _hist_count("quant.dequant_seconds") > dq0
    if fp["host_bytes"] and q["host_bytes"]:
        assert q["host_bytes"] < fp["host_bytes"]
    match = float(np.mean([np.mean(a[3 * BLOCK:] == b[3 * BLOCK:])
                           for a, b in zip(fp["outs"], q["outs"])]))
    assert match >= 0.99, match


def test_model_identity_folds_calibration_digest(sharp_lm):
    base = model_identity(sharp_lm)
    assert ":q" not in base
    sharp_lm.calibrate_cachekv_int8(
        np.random.RandomState(0).randint(0, 128, (2, 32)))
    with_scales = model_identity(sharp_lm)
    assert with_scales.startswith(base) and ":q" in with_scales
    assert model_identity(sharp_lm) == with_scales     # stable
    # calibration drift changes the identity -> a durable resume under
    # different scales degrades to a full re-prefill, never a wrong
    # dequantize
    sharp_lm._cachekv_scales[0] = {
        k: np.asarray(v) * 1.5
        for k, v in sharp_lm._cachekv_scales[0].items()}
    assert model_identity(sharp_lm) != with_scales


def test_session_resume_drill_quantized_demotion_corrupt_blob(
        sharp_lm, tmp_path):
    """Pause -> churn demotes the pinned chain as int8 blobs all the way
    to disk -> every blob is corrupted -> resume still resolves the
    manifest, every promotion fails (audited), and the continuation
    degrades to a full fp prefill that is token-exact vs the
    uninterrupted conversation."""
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    base1 = _ref(sharp_lm, prompt, 6)
    base2 = _ref(sharp_lm, np.concatenate([base1, cont]), 6)

    disk = os.path.join(str(tmp_path), "kv_disk")
    bt = _tiered(sharp_lm, tmp_path, host_bytes=2 * 4400,  # ~2 int8 blobs
                 tier_quant="int8", disk_kv_dir=disk, disk_kv_gib=0.01,
                 session_store=os.path.join(str(tmp_path), "sessions"))
    try:
        with paddle.no_grad():
            rid = bt.submit(prompt, 6)
            out1 = bt.run_until_done(max_steps=60000)[rid]
            np.testing.assert_array_equal(out1, base1)
            assert bt.pause_session("conv", out1) is True
            _churn(bt)
            pins = bt._session_pins["conv"]
            res = {n.residency for n in pins}
            # pin-through-demotion held: off device, never dropped
            assert res <= {"host", "disk"} and res, res
            assert bt.prefix_cache.stats()["session_pin_drops"] == 0
            # corrupt every blob in BOTH tiers (recorded sizes stay, so
            # the byte-accounting audit still balances)
            blobs = glob.glob(os.path.join(disk, "kv_*.npz"))
            assert blobs
            for p in blobs:
                with open(p, "wb") as f:
                    f.write(b"not an npz")
            ht = bt.prefix_cache.host_tier
            for k in list(ht.keys()):
                ht._blobs[k] = (object(), ht.nbytes_of(k))
            toks = bt.resume_session("conv")
            np.testing.assert_array_equal(toks, out1)  # manifest path
            fails0 = bt.prefix_cache.stats()["promotion_failures"]
            rid2 = bt.submit(np.concatenate([toks, cont]), 6)
            out2 = bt.run_until_done(max_steps=60000)[rid2]
            # degraded to full prefill -> fp numerics -> bitwise exact
            np.testing.assert_array_equal(out2, base2)
            assert bt.prefix_cache.stats()["promotion_failures"] > fails0
            bt.audit_pages()
    finally:
        bt.close()


def test_session_resume_rides_quantized_promotion(sharp_lm, tmp_path):
    """Same drill without corruption: the resume promotes the int8
    blobs back (dequantizing on install) and the continuation stays
    within the quality bound of the uninterrupted conversation."""
    rng = np.random.RandomState(29)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    base1 = _ref(sharp_lm, prompt, 6)
    base2 = _ref(sharp_lm, np.concatenate([base1, cont]), 6)

    bt = _tiered(sharp_lm, tmp_path, host_bytes=6 * 16384,
                 tier_quant="int8",
                 session_store=os.path.join(str(tmp_path), "sessions"))
    try:
        with paddle.no_grad():
            rid = bt.submit(prompt, 6)
            out1 = bt.run_until_done(max_steps=60000)[rid]
            np.testing.assert_array_equal(out1, base1)
            assert bt.pause_session("conv", out1) is True
            _churn(bt)
            pins = bt._session_pins["conv"]
            assert "gone" not in {n.residency for n in pins}
            toks = bt.resume_session("conv")
            np.testing.assert_array_equal(toks, out1)
            rid2 = bt.submit(np.concatenate([toks, cont]), 6)
            out2 = bt.run_until_done(max_steps=60000)[rid2]
            assert bt.prefix_cache.stats()["promotions"] > 0
            # quantized promotion is an approximation: the bound is the
            # match rate, not bitwise equality (fp fallbacks stay exact)
            assert float(np.mean(out2[-6:] == base2[-6:])) >= 0.99
            bt.audit_pages()
            bt.prefix_cache.audit_tiers()
    finally:
        bt.close()


# -- tooling ------------------------------------------------------------------

def test_bench_guard_quant_lane_gates_speed_and_match(tmp_path):
    hist = [410.0, 430.0, 425.0, 440.0]

    def write(rnd, value, match):
        (tmp_path / f"BENCH_QUANT_r{rnd:02d}.json").write_text(
            json.dumps({"metric": "quant_serving_decode_tokens_per_sec",
                        "value": value, "unit": "tokens/s",
                        "detail": {"tpu": False,
                                   "token_match_rate": match}}))

    for i, v in enumerate(hist):
        write(i, v, 1.0)

    def guard():
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_guard.py"),
             "--check", "--dir", str(tmp_path), "--json"],
            capture_output=True, text=True)

    ok = guard()
    assert ok.returncode == 0, ok.stdout + ok.stderr
    report = json.loads(ok.stdout)
    speed_key = "quant:quant_serving_decode_tokens_per_sec/cpu"
    match_key = "quant:quant_token_match_rate/cpu"
    assert report["series"][speed_key]["status"] == "pass"
    assert report["series"][match_key]["status"] == "pass"
    assert all(k.startswith("quant:") for k in report["series"])
    # a tokens/s collapse gates
    write(4, 0.8 * hist[-1], 1.0)
    bad = guard()
    assert bad.returncode == 1
    assert json.loads(bad.stdout)["series"][speed_key]["status"] == \
        "regression"
    # a QUALITY collapse gates even with the speed headline flat: the
    # synthesized match series fails as loudly as the tokens/s one
    write(4, hist[-1], 0.85)
    bad2 = guard()
    assert bad2.returncode == 1
    assert json.loads(bad2.stdout)["series"][match_key]["status"] == \
        "regression"


def _dump_prefix_stats(tmp_path, series):
    """Run telemetry_dump --prefix-stats over a hand-written one-rank
    spool holding exactly ``series`` (the process-global registry would
    leak counters from the serving tests above)."""
    import importlib.util
    spool = tmp_path / "rank00000.jsonl"
    lines = [{"kind": "meta", "rank": 0, "world_size": 1, "host": "h",
              "pid": 1, "t": 0.0},
             {"kind": "metrics", "t": 1.0, "series": series}]
    spool.write_text("".join(json.dumps(l) + "\n" for l in lines))
    spec = importlib.util.spec_from_file_location(
        "telemetry_dump", os.path.join(REPO, "tools", "telemetry_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, spool


def test_telemetry_dump_prefix_stats_spill_columns(tmp_path, capsys):
    base = [{"name": "serving.prefix_hit_tokens", "type": "counter",
             "value": 80},
            {"name": "serving.prefix_miss_tokens", "type": "counter",
             "value": 20}]
    quant = base + [
        {"name": "serving.prefix_spill_raw_bytes", "type": "counter",
         "value": 65536},
        {"name": "serving.prefix_spill_blob_bytes", "type": "counter",
         "value": 16640},
        {"name": "serving.kv_host_bytes", "type": "gauge",
         "value": 16640}]
    mod, _ = _dump_prefix_stats(tmp_path, quant)
    assert mod.main(["--fleet", str(tmp_path), "--prefix-stats"]) == 0
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines()
                if l.startswith("# fleet prefix-stats "))
    stats = json.loads(line[len("# fleet prefix-stats "):])
    assert stats["spill_raw_bytes"] == 65536
    assert stats["spill_blob_bytes"] == 16640
    assert stats["spill_compression"] == pytest.approx(3.94, abs=0.01)
    assert stats["host_blob_bytes"] == 16640

    # legacy fleets (no spill counters) keep the line byte-identical:
    # none of the new columns appear
    mod2, _ = _dump_prefix_stats(tmp_path, base)
    assert mod2.main(["--fleet", str(tmp_path), "--prefix-stats"]) == 0
    out2 = capsys.readouterr().out
    line2 = next(l for l in out2.splitlines()
                 if l.startswith("# fleet prefix-stats "))
    stats2 = json.loads(line2[len("# fleet prefix-stats "):])
    assert "spill_raw_bytes" not in stats2
    assert "spill_compression" not in stats2
    assert "host_blob_bytes" not in stats2
    assert stats2["hit_rate"] == 0.8


def test_ledger_charges_dequant_waste():
    from paddle_tpu.observability.ledger import (GoodputLedger,
                                                 WASTE_CATEGORIES)
    assert "dequant" in WASTE_CATEGORIES

    class Stub:
        def snapshot(self):
            return [{"name": "quant.dequant_seconds", "type": "histogram",
                     "sum": 0.25, "count": 3},
                    {"name": "other.series", "type": "histogram",
                     "sum": 9.0, "count": 1}]

    led = GoodputLedger()
    assert led.add_dequant_from_registry(Stub()) == pytest.approx(0.25)
    assert led.waste["dequant"] == pytest.approx(0.25)
    assert led.chip_s == pytest.approx(0.25)
    assert led.goodput_frac == pytest.approx(0.0)   # all-waste ledger
    # empty registry is a no-op
    led2 = GoodputLedger()

    class Empty:
        def snapshot(self):
            return []

    assert led2.add_dequant_from_registry(Empty()) == 0.0
    assert led2.waste["dequant"] == 0.0
