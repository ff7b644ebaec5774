"""Serving GPT-2 with the full round-3 toolkit:

- variable-length prompts through sequence BUCKETS (O(log n) executables
  instead of one compile per length),
- incremental decode over the dense KV cache with ONE compiled step,
- the paged (vLLM-style) block-cache route for memory-proportional caches.

(For weight-only int8 serving see 05_serve_gpt2_weight_only_int8.py.)

Run: python examples/07_paged_kv_serving.py
"""
import json
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import jit
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM


def main():
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=1024, hidden_size=256, num_hidden_layers=4,
                     num_attention_heads=8, max_position_embeddings=256,
                     dropout=0.0)
    model = GPT2ForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)

    # 1) bucketed prefill-style forward: three different prompt lengths,
    #    two executables (buckets 64 and 128)
    bucketed = jit.to_static(model.forward, seq_buckets=(64, 128))
    with paddle.no_grad():
        for s in (40, 57, 100):
            ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (1, s)))
            logits = bucketed(ids)
            assert logits.shape[1] == s
    print("bucketed forward: 3 prompt lengths served (lengths pad to "
          "buckets 64/128 and reuse the bucket's executable)")

    # 2) incremental decode, dense KV cache, compiled step
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 32)))
    with paddle.no_grad():
        step = jit.to_static(model.decode_step)
        model.generate(ids, max_new_tokens=2, decode_fn=step)  # compile/warm
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=32, decode_fn=step)
        dense_dt = time.perf_counter() - t0
    print(f"dense-cache generate: {out.shape[1] - 32} new tokens "
          f"in {dense_dt:.2f}s")

    # 3) paged block cache, compiled step
    with paddle.no_grad():
        pstep = jit.to_static(model.paged_decode_step)
        model.generate_paged(ids, max_new_tokens=2, block_size=32,
                             decode_fn=pstep)  # compile/warm
        t0 = time.perf_counter()
        out_p = model.generate_paged(ids, max_new_tokens=32, block_size=32,
                                     decode_fn=pstep)
        paged_dt = time.perf_counter() - t0
    assert out_p.numpy().tolist() == out.numpy().tolist(), \
        "paged and dense routes must be token-exact"
    print(f"paged generate (token-exact match): {paged_dt:.2f}s")

    # 4) the full serving engine: paged continuous batching with chunked
    #    prefill — a new prompt streams through one fixed-width executable
    #    at a step boundary, then joins the running decode batch
    from paddle_tpu.inference import PagedContinuousBatcher
    batcher = PagedContinuousBatcher(model, max_batch=4, s_max=256,
                                     block_size=32, prefill_chunk=64,
                                     policy="ondemand")
    rng = np.random.RandomState(0)
    reqs = [rng.randint(0, model.config.vocab_size, (n,))
            for n in (37, 100, 180, 64)]

    def run_batched():
        # fresh counters per scenario run: the retry below reuses this
        # batcher, and blended two-run stats would skew the JSON line
        batcher.reset_stats()
        rids = [batcher.submit(p, 24) for p in reqs]
        outs = batcher.run_until_done()
        return [outs[r] for r in rids]

    def run_solos():
        return [model.generate(paddle.to_tensor(p[None].astype("int64")),
                               max_new_tokens=24).numpy()[0] for p in reqs]

    outs = run_batched()
    solos = run_solos()
    if any(o.tolist() != s.tolist() for o, s in zip(outs, solos)):
        # one retry of the WHOLE batched scenario + fresh solos: heavy
        # host load can flip argmax near-ties in the CPU backend
        # (tests/test_paged_batching.py docstring) on either side. The
        # retry re-runs all requests BATCHED TOGETHER so a real
        # cross-request interference bug still reproduces and aborts.
        print("token mismatch once — retrying the full batched scenario "
              "(load can flip argmax near-ties on the CPU backend)")
        outs = run_batched()
        solos = run_solos()
        for o, s in zip(outs, solos):
            assert o.tolist() == s.tolist(), \
                "continuous batching must be token-exact vs solo"
    stats = batcher.stats()
    print(f"continuous batching: {stats['completed_requests']} requests, "
          f"{stats['generated_tokens']} tokens, "
          f"occupancy {stats['mean_active_slots']:.2f}, "
          f"{stats['tokens_per_sec']:.1f} tok/s")

    print(json.dumps({"metric": "serving_example",
                      "dense_s": round(dense_dt, 3),
                      "paged_s": round(paged_dt, 3),
                      "batcher_tok_s": round(stats["tokens_per_sec"], 1)}))


if __name__ == "__main__":
    main()
