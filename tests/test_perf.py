"""Perf layer: bucket ladders, compile counters, recompile guards,
coalesced transfer, device prefetcher.

The recompile guards are the PR's acceptance tests: N steady-state train
steps and a mixed-length serving run must stop compiling after warmup —
``compile.miss`` flat IS the "kill the recompiles" contract, enforced
here so a future change that reintroduces per-shape churn fails CI.

Tier-1 lane (marker: perf) under a time budget — everything here runs on
tiny shapes.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.perf import (BucketLadder, ShapeBuckets, compile_metrics,
                             resolve_ladder)
from paddle_tpu.perf.buckets import pad_amount

pytestmark = pytest.mark.perf

TIME_BUDGET_S = 60


@pytest.fixture(autouse=True)
def _time_budget():
    t0 = time.perf_counter()
    yield
    assert time.perf_counter() - t0 < TIME_BUDGET_S, \
        "perf test exceeded its time budget"


def _misses():
    return compile_metrics()["compile_cache_misses"]


# -- bucket ladders ----------------------------------------------------------

def test_pow2_ladder_rungs():
    assert list(BucketLadder.pow2(1, 32)) == [1, 2, 4, 8, 16, 32]
    # hi that is not a power of two becomes the top rung
    assert list(BucketLadder.pow2(1, 48))[-1] == 48


def test_fixed_ladder_rungs():
    assert list(BucketLadder.fixed(16, 64)) == [16, 32, 48, 64]
    assert list(BucketLadder.fixed(16, 40)) == [16, 32, 40]


def test_bucket_lookup_and_identity_above_top():
    ladder = BucketLadder([4, 8, 16])
    assert ladder.bucket(1) == 4
    assert ladder.bucket(8) == 8
    assert ladder.bucket(9) == 16
    # above the top rung: identity, never truncation
    assert ladder.bucket(17) == 17
    assert ladder.bucket(1000) == 1000
    # non-positive sizes pass through
    assert ladder.bucket(0) == 0
    assert ladder.bucket(-3) == -3


def test_custom_ladder_must_be_strictly_increasing():
    with pytest.raises(ValueError):
        BucketLadder([4, 4, 8])
    with pytest.raises(ValueError):
        BucketLadder([8, 4])
    with pytest.raises(ValueError):
        BucketLadder([])
    with pytest.raises(ValueError):
        BucketLadder([0, 4])


def test_resolve_ladder_specs():
    assert resolve_ladder(None) is None
    assert list(resolve_ladder("pow2", hi=16)) == [1, 2, 4, 8, 16]
    assert list(resolve_ladder("fixed:8", hi=24)) == [8, 16, 24]
    assert list(resolve_ladder([16, 4, 8])) == [4, 8, 16]  # sorted
    ladder = BucketLadder([2, 4, 64])
    assert list(resolve_ladder(ladder, hi=8)) == [2, 4, 8]  # capped
    with pytest.raises(ValueError):
        resolve_ladder("fixed:8")  # needs hi
    with pytest.raises(ValueError):
        resolve_ladder("fibonacci", hi=8)


def test_pad_amount():
    ladder = BucketLadder([4, 8])
    assert pad_amount(ladder, 3) == 1
    assert pad_amount(ladder, 4) == 0
    assert pad_amount(ladder, 100) == 0  # out of ladder: no padding
    assert pad_amount(None, 3) == 0


def test_shape_buckets_empty_and_per_axis():
    sb = ShapeBuckets({0: "pow2", 1: [128, 256]}, hi={0: 8})
    assert sb.bucket_for(()) == ()  # empty (scalar) shape maps to itself
    assert sb.bucket_for((3, 100)) == (4, 128)
    assert sb.bucket_for((3, 300, 7)) == (4, 300, 7)  # axis 1 above ladder;
    # axis 2 has no ladder -> passthrough


# -- recompile guards (the acceptance tests) ---------------------------------

def test_train_steps_stop_compiling_after_warmup():
    """10 steady-state fused train steps: compile.miss must be flat after
    step 1 (one discovery/build miss, then pure cache hits)."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 1))
    model = paddle.hapi.Model(net)
    model.prepare(optimizer=opt.SGD(learning_rate=0.1,
                                    parameters=net.parameters()),
                  loss=nn.MSELoss(), jit=True)
    rng = np.random.RandomState(0)
    x = rng.rand(8, 4).astype("float32")
    y = rng.rand(8, 1).astype("float32")

    model.train_batch([x], [y])  # warmup: the one allowed miss
    m_after_warmup = _misses()
    losses = [model.train_batch([x], [y])[0] for _ in range(10)]
    assert len(losses) == 10
    assert all(np.isfinite(l) for l in losses)
    assert _misses() == m_after_warmup, \
        "steady-state train steps recompiled — the recompile bug is back"


def test_serving_mixed_lengths_bounded_compiles():
    """Mixed prompt lengths drawn from <= 3 buckets: after the first wave,
    a second wave of new lengths from the SAME buckets adds zero misses."""
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    from paddle_tpu.inference.serving import ContinuousBatcher

    paddle.seed(0)
    cfg = GPT2Config(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    bat = ContinuousBatcher(m, max_batch=4, s_max=32, compile=True)
    rng = np.random.RandomState(0)

    # lengths spanning exactly 3 pow2 buckets: {4}, {5..8}, {9..16}
    for L in [3, 5, 9, 4, 6, 12]:
        bat.submit(rng.randint(1, 96, size=L), max_new_tokens=3)
    out = bat.run_until_done()
    assert len(out) == 6
    m_wave1 = _misses()

    # new lengths, same buckets -> zero new compiles
    for L in [4, 7, 11, 8, 16]:
        bat.submit(rng.randint(1, 96, size=L), max_new_tokens=3)
    out = bat.run_until_done()
    assert len(out) == 5
    assert _misses() == m_wave1, \
        "serving recompiled for prompt lengths inside known buckets"


def test_serving_pad_waste_metric_counts():
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    from paddle_tpu.inference.serving import ContinuousBatcher
    from paddle_tpu.observability.metrics import get_registry

    paddle.seed(0)
    cfg = GPT2Config(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    # rung-labeled since round 13: waste is attributable per resolved
    # bucket without re-deriving the ladder
    waste = get_registry().counter("serving.bucket_pad_waste", "test",
                                   labelnames=("rung",)).labels(rung="8")
    before = waste.value
    bat = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
    bat.submit(np.arange(1, 6), max_new_tokens=2)   # len 5 -> bucket 8: +3
    bat.submit(np.arange(1, 9), max_new_tokens=2)   # len 8 -> exact rung
    bat.run_until_done()
    assert waste.value - before == 3


def test_bucketed_serving_matches_unbucketed():
    """Bucket padding must not change generated tokens (greedy)."""
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    from paddle_tpu.inference.serving import ContinuousBatcher

    paddle.seed(0)
    cfg = GPT2Config(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 96, size=L) for L in (3, 5, 11)]

    outs = {}
    for buckets in ("pow2", None):
        bat = ContinuousBatcher(m, max_batch=4, s_max=32, compile=False,
                                prompt_buckets=buckets)
        rids = [bat.submit(p, max_new_tokens=4) for p in prompts]
        res = bat.run_until_done()
        outs[buckets] = [res[r] for r in rids]
    for a, b in zip(outs["pow2"], outs[None]):
        np.testing.assert_array_equal(a, b)


# -- roofline model (tools/roofline.py) ---------------------------------------
# (the persistent-cache placement test lives in tests/test_chip_smoke.py)

def test_roofline_model_runs_and_is_compute_bound():
    """tools/roofline.py: pin the schema and the analytic conclusion —
    every config is COMPUTE-bound on v5e with an MFU ceiling far above the
    0.50 bar — so a sub-0.5 measurement indicts kernel/fusion efficiency,
    not HBM bandwidth."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable,
                          os.path.join(repo, "tools", "roofline.py")],
                         cwd=repo, capture_output=True, text=True,
                         timeout=60)
    assert out.returncode == 0, out.stderr
    with open(os.path.join(repo, "ROOFLINE.json")) as f:
        rec = json.load(f)
    names = {c["config"] for c in rec["configs"]}
    assert {"large", "medium", "small"} <= names
    for c in rec["configs"]:
        assert c["bound"] == "compute", c
        assert c["measured_mfu_ceiling"] > 0.5, c
        assert c["hbm_bytes"]["total"] > 0


def test_roofline_large_config_mirrors_bench():
    """tools/roofline.py hardcodes the bench dimensions; if bench.py is
    retuned without updating the mirror, the roofline table silently
    describes a config that no longer runs. bench.py runs one size, the
    roofline's "large"."""
    import importlib.util
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(name, *path):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(repo, *path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    bench = load("bench_mod", "bench.py")       # imports no jax
    roof = load("roofline_mod", "tools", "roofline.py")
    [large] = [c for c in roof.BENCH_CONFIGS if c[0] == "large"]
    _name, V, H, I, L, heads, kvh, batch, seq, remat = large
    m = bench.MODEL
    assert (V, H, I, L, heads, kvh, batch, seq, remat) == (
        m["vocab_size"], m["hidden_size"], m["intermediate_size"],
        m["num_hidden_layers"], m["num_attention_heads"],
        m["num_key_value_heads"], bench.BATCH, bench.SEQ, "selective"), (
        "bench.py and tools/roofline.py disagree: update the mirror")


# -- input pipeline ----------------------------------------------------------

def test_coalesced_device_put_roundtrip():
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.perf.prefetch import coalesced_device_put

    batch = {"x": np.arange(6, dtype="float32").reshape(2, 3),
             "y": [np.ones(2, dtype="int64"), "tag"],
             "n": 7}
    out = coalesced_device_put(batch)
    assert isinstance(out["x"], Tensor)
    np.testing.assert_array_equal(out["x"].numpy(), batch["x"])
    assert isinstance(out["y"][0], Tensor)
    np.testing.assert_array_equal(out["y"][0].numpy(), batch["y"][0])
    assert out["y"][1] == "tag"
    assert out["n"] == 7


def test_device_prefetcher_delivers_in_order_and_closes():
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.perf.prefetch import DevicePrefetcher

    batches = [{"x": np.full((2, 2), i, dtype="float32")} for i in range(6)]
    pf = DevicePrefetcher(iter(batches), depth=2)
    got = list(pf)
    assert len(got) == 6
    for i, b in enumerate(got):
        assert isinstance(b["x"], Tensor)
        assert float(b["x"].numpy()[0, 0]) == float(i)
    pf.close()  # idempotent


def test_device_prefetcher_surfaces_source_errors():
    from paddle_tpu.perf.prefetch import DevicePrefetcher

    def boom():
        yield {"x": np.zeros(2, dtype="float32")}
        raise RuntimeError("source died")

    pf = DevicePrefetcher(boom(), depth=2)
    next(pf)
    with pytest.raises(RuntimeError, match="source died"):
        while True:
            next(pf)


def test_dataloader_prefetch_to_device_yields_tensors():
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.io.dataloader import DataLoader

    data = [(np.full(3, i, dtype="float32"), np.int64(i)) for i in range(10)]
    dl = DataLoader(data, batch_size=4, prefetch_to_device=True)
    seen = []
    for xb, yb in dl:
        assert isinstance(xb, Tensor) and isinstance(yb, Tensor)
        seen += yb.numpy().tolist()
    assert seen == list(range(10))


def test_dataloader_tail_batch_bucketing():
    from paddle_tpu.io.dataloader import DataLoader

    data = [(np.full(2, i, dtype="float32"), np.int64(i)) for i in range(11)]
    dl = DataLoader(data, batch_size=4, batch_buckets="pow2")
    shapes = [tuple(xb.shape) for xb, _ in dl]
    # tail of 3 pads to the bucket rung 4 by repeating the last sample
    assert shapes == [(4, 2), (4, 2), (4, 2)]
    *_, (xb, yb) = iter(DataLoader(data, batch_size=4,
                                   batch_buckets="pow2"))
    assert yb.numpy().tolist() == [8, 9, 10, 10]


def test_async_loader_close_during_inflight_transfer(monkeypatch):
    """close() while a transfer is IN FLIGHT: the issued transfer is
    allowed to land, queued-but-unissued work is cancelled typed, and —
    the lock-discipline invariant close() documents — the intake lock
    is never held across the worker-join deadline. The witness's
    hold-time accounting proves the last part: with a payload that
    stalls the worker ~0.2s, a close() that awaited the join under
    ``AsyncLoader._intake`` would show a comparable max hold."""
    import threading

    from paddle_tpu.perf.prefetch import AsyncLoader, TransferCancelled
    from paddle_tpu.utils import locks

    monkeypatch.setenv("PADDLE_LOCK_WITNESS", "1")
    locks.reset_witness()
    ld = AsyncLoader(depth=4, workers=1)
    entered = threading.Event()

    def slow_payload():
        entered.set()
        time.sleep(0.2)
        return {"x": np.ones(2, dtype="float32")}

    inflight = ld.submit(slow_payload)
    assert entered.wait(2.0), "worker never picked up the transfer"
    queued = ld.submit({"y": np.zeros(2, dtype="float32")})
    ld.close(timeout=2.0)

    got = inflight.result(timeout=2.0)
    np.testing.assert_array_equal(np.asarray(got["x"]), np.ones(2))
    with pytest.raises(TransferCancelled):
        queued.result(timeout=2.0)

    held = locks.get_witness().max_hold("AsyncLoader._intake")
    assert held < 0.1, (
        f"intake lock held {held:.3f}s — close() awaited the worker "
        f"join (or the in-flight transfer) while holding it")


def test_device_prefetcher_close_during_inflight_transfer(monkeypatch):
    """close() while the feeder is INSIDE a transfer: close must return
    within its bound, retire cleanly once the transfer lands, and — per
    the intake-lock discipline — never await the feeder join while
    holding ``DevicePrefetcher._intake`` (witness hold accounting)."""
    import threading

    from paddle_tpu.perf.prefetch import DevicePrefetcher
    from paddle_tpu.utils import locks

    monkeypatch.setenv("PADDLE_LOCK_WITNESS", "1")
    locks.reset_witness()
    entered = threading.Event()

    def slow_transfer(batch):
        entered.set()
        time.sleep(0.2)
        return batch

    batches = [{"x": np.full(2, i, dtype="float32")} for i in range(8)]
    pf = DevicePrefetcher(iter(batches), depth=1, transfer=slow_transfer)
    assert entered.wait(2.0), "feeder never started a transfer"
    t0 = time.perf_counter()
    pf.close(timeout=2.0)
    assert time.perf_counter() - t0 < 2.0
    assert pf._retired and not pf._thread.is_alive()
    pf.close()  # idempotent after retirement

    held = locks.get_witness().max_hold("DevicePrefetcher._intake")
    assert held < 0.1, (
        f"intake lock held {held:.3f}s — close() awaited the feeder "
        f"join while holding it")
