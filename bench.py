"""Benchmark: Llama training tokens/sec/chip (BASELINE.md north-star metric).

One process, one size, one chip. It asserts a TPU before anything else,
runs the train step through ``jit.TrainStep``, and prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {"platform", "kind", "count"}, "detail": {...}}

vs_baseline is measured MFU / 0.50 — the north-star bar is ">50% of H100
tokens/sec/chip", which at matched parallelism is an efficiency bar: 1.0 means
the model FLOPs utilization on this chip reaches 50%.

There is no CPU branch and no second size: with no TPU, or on any exception
(profile and telemetry included), the process exits non-zero and prints no
result line. It stays a single training number until ROADMAP S0 rewrites it
into cells.
"""
from __future__ import annotations

import json
import os
import sys
import time

_T0 = time.perf_counter()
_REPO_DIR = os.path.dirname(os.path.abspath(__file__))

# Sized to one 16 GB v5e: ~0.55B params → 7.7 GB of bf16 weight + fp32
# master + Adam m/v; seq 2048 through the flash-attention Pallas kernel;
# head_dim 128 and hidden 1536 (12×128 lanes) to fill the MXU.
# tools/roofline.py mirrors these as its "large" config (tests/test_perf.py
# pins the correspondence).
MODEL = dict(vocab_size=32000, hidden_size=1536, intermediate_size=4096,
             num_hidden_layers=16, num_attention_heads=12,
             num_key_value_heads=12, max_position_embeddings=2048)
BATCH, SEQ, ITERS = 4, 2048, 15


def _progress(msg):
    print(f"[bench +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


def main():
    import jax
    _progress("backend init")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; JAX reports "
                         f"{dev.platform!r} ({dev.device_kind})")

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp, jit, optimizer
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.perf import compile_cache as perf_cc
    from paddle_tpu.utils.flops import peak_device_flops

    peak = peak_device_flops(dev)   # raises for a kind not in the table
    _progress(f"compile cache at {perf_cc.enable_persistent_cache()}")

    # recompute "selective" (dots_with_no_batch_dims_saveable), NOT "full":
    # full remat replays the whole forward in the backward — ~25% of the
    # step is uncounted FLOPs and measured MFU caps at 0.75× the hardware
    # utilization. Selective keeps matmul outputs resident (~4.2 GB at
    # batch 4 × seq 2048) and replays only the cheap elementwise chains.
    # scan_layers: the decoder stack compiles as ONE lax.scan body, so
    # compile time is O(1) in depth instead of O(16 layers).
    cfg = LlamaConfig(**MODEL, scan_layers=True, use_recompute=True,
                      recompute_granularity="selective")
    # measure flash (block_q, block_k) tilings once per shape and run the
    # number at the winner (autotune is trace-safe)
    paddle.set_flags({"FLAGS_flash_autotune": True})

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters(), multi_precision=True)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    n_params = model.num_params()

    def loss_fn(ids, labels):
        _, loss = model(ids, labels=labels)
        return loss

    # op-level observatory: capture the step executable's cost profile
    # at its warm transitions (OPPROF_r*.json + the opprof: guard lane)
    from paddle_tpu.observability import opprof
    opprof.enable()
    opprof.reset_captures()

    step = jit.TrainStep(loss_fn, opt, opprof_label="bench.train_step")

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (BATCH, SEQ)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (BATCH, SEQ)))

    # Eager discovery pass on a tiny batch (the unfused eager tape holds every
    # vjp residual — keep it off the big shape), then compile + warm the real
    # shape (the pure step is shape-polymorphic; jit retraces per shape).
    warm_ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (1, 128)))
    warm_labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (1, 128)))
    _progress(f"model built ({n_params/1e6:.0f}M params); eager discovery "
              f"pass starting")
    step(warm_ids, warm_labels)
    _progress("discovery done; compiling the fused train step")
    loss = step(ids, labels)
    float(loss)
    _progress("compiled; timing")

    from paddle_tpu.observability import span

    t0 = time.perf_counter()
    for _ in range(ITERS):
        with span("bench_train_step"):
            loss = step(ids, labels)
    final_loss = float(loss)  # blocks on the device
    elapsed = time.perf_counter() - t0

    tokens_per_sec = BATCH * SEQ * ITERS / elapsed
    # one process, so the perf counters ARE this bench's compile story:
    # misses = programs built, compile_time_s = trace+compile spend
    compile_stats = perf_cc.compile_metrics()

    # Model FLOPs: 6*P per token (fwd+bwd) + attention score/context terms
    att_flops = 12 * cfg.num_hidden_layers * cfg.hidden_size * SEQ
    flops_per_token = 6 * n_params + att_flops
    mfu = tokens_per_sec * flops_per_token / peak

    detail = {
        "model": "llama",
        "tpu": True,
        "params": n_params,
        "batch": BATCH,
        "seq": SEQ,
        "iters": ITERS,
        "final_loss": round(final_loss, 4),
        "mfu": round(mfu, 4),
        "steady_step_s": round(elapsed / ITERS, 5),
        **compile_stats,
        "amp": "O2 bf16 + fp32 master",
        "recompute": cfg.recompute_granularity,
        "flash": bool(paddle.get_flags(
            ["FLAGS_use_pallas_attention"])["FLAGS_use_pallas_attention"]),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # op-level profile: split the roofline gap per op class, embed the
    # top-k class cost table + executable fingerprint, persist the OPPROF
    # artifact
    from paddle_tpu.observability import roofline_attr
    attr = roofline_attr.observe_train_step(
        elapsed / ITERS, observed_mfu=mfu, tokens=BATCH * SEQ,
        params=n_params)
    gap_split = opprof.publish_gap_attribution(attr) if attr else None
    summary = opprof.bench_summary()
    if summary is not None:
        detail["opprof"] = summary
        opp_path = opprof.write_artifact(
            _REPO_DIR, tpu=True, gap_attribution=gap_split,
            extra={"bench_step_s": round(elapsed / ITERS, 5),
                   "bench_mfu": round(mfu, 4)})
        if opp_path:
            detail["opprof"]["artifact"] = os.path.basename(opp_path)
            _progress(f"op profile: {opp_path} "
                      f"(top {summary['top_op_classes'][:2]})")
    # telemetry snapshot rides alongside (stderr + file only — stdout is
    # the one-JSON-line contract)
    from paddle_tpu.observability import load_jsonl, write_jsonl
    snap_path = os.path.join(_REPO_DIR, "BENCH_TELEMETRY.jsonl")
    write_jsonl(snap_path, extra={"bench": "llama", "tpu": True})
    detail["telemetry_series"] = len(load_jsonl(snap_path))
    _progress(f"telemetry snapshot: {snap_path} "
              f"({detail['telemetry_series']} series)")
    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.50, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "detail": detail,
    }), flush=True)


if __name__ == "__main__":
    main()
