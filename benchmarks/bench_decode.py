"""Serving decode benchmark (VERDICT r2 #6 + r3 #6: the serving path).

Headline number = steady-state tokens/sec of the PAGED CONTINUOUS BATCHER
with fused admission — the actual serving configuration (vLLM-style paged
KV blocks, chunked prefill, decode+prefill in one executable). Same JSON
contract as bench.py: ONE line
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": ...}
with detail.tpu / detail.captured_at.

Variant sweep in detail (reference analog: the inference engine's
performance surface, fluid/inference/api/analysis_predictor.h:100):
  - naive full-recompute, eager KV cache, paged eager, int8 compiled —
    CPU only (regression tracking)
  - kv_cache_compiled: ONE jit.to_static executable reused per step
  - batcher / fused batcher: tokens/sec + slot occupancy from the
    batcher's own stats counters

Runs on the backend JAX reports and tags the line with it (detail.tpu);
a CPU line is a regression check of counts, never a device number.
"""
import json
import time

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import jit, nn
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM


def _steady_rate(step_fn, iters=32, warmup=4):
    """steps/sec of a repeated single-token step (batch handled inside)."""
    for _ in range(warmup):
        step_fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        step_fn()
    dt = time.perf_counter() - t0
    return iters / dt


def main():
    paddle.seed(0)
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        # GPT-2-124M-class serving config: big enough that the decode step
        # is real MXU work, small enough that its few executables compile
        # in a couple of minutes.
        from paddle_tpu.perf.compile_cache import enable_persistent_cache
        enable_persistent_cache()
        cfg = GPT2Config(vocab_size=32000, hidden_size=768,
                         num_hidden_layers=12, num_attention_heads=12,
                         max_position_embeddings=1024, dropout=0.0)
        batch, ctx, s_max = 8, 256, 512
        full_sweep = False
    else:
        cfg = GPT2Config(vocab_size=2048, hidden_size=256,
                         num_hidden_layers=4, num_attention_heads=8,
                         max_position_embeddings=512, dropout=0.0)
        batch, ctx, s_max = 4, 128, 256
        full_sweep = True
    model = GPT2ForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, ctx)))

    detail = {"params": model.num_params(), "batch": batch, "context": ctx,
              "cache": s_max, "tpu": on_tpu}
    with paddle.no_grad():
        if full_sweep:
            # naive full-recompute step at the starting context length
            def naive_step():
                out = model(ids)
                np.asarray(out._data[:, -1])  # block

            detail["naive_steps_per_s"] = round(_steady_rate(naive_step,
                                                             iters=8), 3)

            # kv-cache eager
            logits, caches, t = model.prefill(ids, s_max)
            tok = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (batch, 1)))
            state = {"caches": caches, "t": t}

            def eager_step():
                _, state["caches"], state["t"] = model.decode_step(
                    tok, state["caches"], state["t"])

            detail["kv_cache_eager_steps_per_s"] = round(
                _steady_rate(eager_step, iters=8), 3)

            # paged block cache (vLLM-style) decode step, eager — measured
            # on the fp32 model so it compares against kv_cache_eager
            _, pstate = model.paged_prefill(ids, block_size=64)
            ptok = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (batch,)))
            pbox = {"s": pstate}

            def paged_step():
                _, pbox["s"] = model.paged_decode_step(ptok, pbox["s"])

            detail["paged_eager_steps_per_s"] = round(
                _steady_rate(paged_step, iters=8), 3)

        # kv-cache compiled (ONE executable reused per step) — every backend
        tok = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, 1)))
        compiled = jit.to_static(model.decode_step)
        _, caches2, t2 = model.prefill(ids, s_max)
        state2 = {"caches": caches2, "t": t2}

        def compiled_step():
            _, state2["caches"], state2["t"] = compiled(
                tok, state2["caches"], state2["t"])

        rate = _steady_rate(compiled_step)
        detail["kv_cache_compiled_steps_per_s"] = round(rate, 3)
        detail["kv_cache_compiled_tokens_per_s"] = round(rate * batch, 2)

        if full_sweep:
            # int8 weight-only variant (mutates `model` in place)
            n_q = nn.quant.quantize_linear_layers(model)
            compiled_q = jit.to_static(model.decode_step)
            _, caches3, t3 = model.prefill(ids, s_max)
            state3 = {"caches": caches3, "t": t3}

            def int8_step():
                _, state3["caches"], state3["t"] = compiled_q(
                    tok, state3["caches"], state3["t"])

            detail["kv_cache_int8_steps_per_s"] = round(
                _steady_rate(int8_step), 3)
            detail["int8_linears"] = n_q

    # continuous batching end-to-end: staggered requests through the
    # paged batcher (compiled donated step + chunked prefill), the actual
    # serving configuration — reports tokens/sec and occupancy from the
    # batcher's own stats counters. Fresh fp model: the int8 pass above
    # may have mutated `model` in place.
    paddle.seed(0)
    serving_model = GPT2ForCausalLM(cfg)
    serving_model.eval()
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    new_toks = 32
    req_lens = [ctx - 17, ctx, ctx + 13, ctx - 5, ctx + 29, ctx]

    def drive(batcher):
        # warmup request compiles the chunk/decode executables, then the
        # counters reset so the measured window is steady-state serving
        batcher.submit(rng.randint(0, cfg.vocab_size, (ctx,)), 8)
        batcher.run_until_done()
        batcher.reset_stats()
        for ln in req_lens:
            batcher.submit(rng.randint(0, cfg.vocab_size, (ln,)), new_toks)
        batcher.run_until_done()
        return batcher.stats()

    b = PagedContinuousBatcher(serving_model, max_batch=batch, s_max=s_max,
                               block_size=64, prefill_chunk=64,
                               policy="ondemand", compile=True)
    s = drive(b)
    detail["batcher_tokens_per_s"] = round(s["tokens_per_sec"], 2)
    detail["batcher_slot_utilization"] = round(s["slot_utilization"], 3)
    detail["batcher_requests"] = s["completed_requests"]

    # fused admission (vLLM unified scheduling): decode + prefill share
    # one executable, so admission no longer pauses decoding. The batcher
    # never mutates weights, so the fp serving model is reusable.
    # decode_block=8 on TPU: pure-decode phases run 8 steps per dispatch
    # with on-device greedy feedback, so the host pays one dispatch and
    # one download per 8 tokens. CPU keeps block=None so its line stays
    # comparable with prior rounds.
    decode_block = 8 if on_tpu else None
    bf = PagedContinuousBatcher(serving_model, max_batch=batch, s_max=s_max,
                                block_size=64, prefill_chunk=64,
                                policy="ondemand", fused_admission=True,
                                decode_block=decode_block,
                                compile=True)
    sf = drive(bf)
    detail["fused_batcher_tokens_per_s"] = round(sf["tokens_per_sec"], 2)
    detail["fused_batcher_slot_utilization"] = round(
        sf["slot_utilization"], 3)
    detail["fused_batcher_steps"] = sf["steps"]
    detail["decode_block"] = decode_block
    detail["decode_blocks_dispatched"] = sf.get("decode_blocks", 0)

    if on_tpu:
        detail["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime())
    # headline = the fused paged batcher, ALWAYS — taking a max would let a
    # fused-admission regression silently hide behind the plain batcher.
    # vs_baseline stays 0.0: the reference publishes no serving figure to
    # normalize against (BASELINE.md).
    detail["occupancy"] = round(sf["slot_utilization"], 3)
    print(json.dumps({
        "metric": "paged_serving_decode_tokens_per_sec",
        "value": round(sf["tokens_per_sec"], 2),
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "detail": detail,
    }))


if __name__ == "__main__":
    main()
