"""Serving-path quantization benchmark: two arms, one bench line.

**Tier-capacity arm** (the ``bench_prefix_churn`` workload, quantized):
the same Zipf churn stream runs tiered twice at the SAME host byte
budget — fp blobs vs ``tier_quant='int8'`` blobs. The quantized arm's
spilled chains cost ~1/4 the bytes (int8 codes + per-head scales vs
fp32), so the budget holds ~4x the chains; the arm reports the measured
capacity ratio (raw spill bytes over as-stored spill bytes), both hit
rates, and generated-token agreement with the fp arm.

**int8-weights arm**: the same decode workload driven twice through the
paged batcher — fp weights vs ``serving_quantize``'d int8 weights (the
model is briefly trained first so logits are sharp; random-init argmax
near-ties flip under any perturbation and would measure the MODEL, not
the quantizer). Reports decode tokens/s, TPOT p50, and the greedy
token-match rate vs fp.

Headline number = the int8-weights arm's decode tokens/s. Detail carries
``token_match_rate`` (the ``quant:`` bench_guard lane gates it as a
second series — a quality regression fails as loudly as a speed one)
and ``tier_capacity_ratio``.

Bench line lands in ``BENCH_QUANT_r<NN>.json`` at the repo root. Same
JSON contract as bench.py: ONE stdout line
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": ...}
vs_baseline stays 0.0 — the reference publishes no comparable figure.
"""
import json
import os
import sys
import time

import numpy as np

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_DIR)

import paddle_tpu as paddle                                    # noqa: E402
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM  # noqa: E402

BLOCK_SIZE = 16
PREFIX_BLOCKS = 3
N_PREFIXES = 16
N_PAGES = 22
MAX_BATCH = 2
S_MAX = 96
TAIL_TOKENS = 5
NEW_TOKENS = 4
N_REQUESTS = 48
ZIPF_A = 0.5
HOST_GIB = 0.25

TRAIN_STEPS = 40           # sharpen logits so greedy argmax is stable
DECODE_PROMPTS = 12
DECODE_NEW = 16


def _model(train: bool = False):
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     max_position_embeddings=128, dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    if train:
        import paddle_tpu.nn.functional as F
        from paddle_tpu import optimizer
        rng = np.random.RandomState(0)
        data = paddle.to_tensor(rng.randint(0, 128, (4, 33)))
        opt = optimizer.AdamW(learning_rate=1e-2,
                              parameters=m.parameters())
        for _ in range(TRAIN_STEPS):
            logits = m(data[:, :-1])
            loss = F.cross_entropy(
                logits.reshape([-1, cfg.vocab_size]),
                data[:, 1:].reshape([-1]))
            loss.backward()
            opt.step()
            opt.clear_grad()
    m.eval()
    return m, cfg


def _churn_workload(vocab):
    rng = np.random.RandomState(0)
    prefixes = [rng.randint(0, vocab, (BLOCK_SIZE * PREFIX_BLOCKS,))
                for _ in range(N_PREFIXES)]
    w = 1.0 / np.arange(1, N_PREFIXES + 1) ** ZIPF_A
    w /= w.sum()
    picks = rng.choice(N_PREFIXES, size=N_REQUESTS, p=w)
    prompts = [np.concatenate([prefixes[p],
                               rng.randint(0, vocab, (TAIL_TOKENS,))])
               for p in picks]
    return prefixes, prompts


def _spill_counters():
    from paddle_tpu.observability import get_registry
    out = {"raw": 0, "blob": 0}
    for s in get_registry().snapshot():
        if s.get("name") == "serving.prefix_spill_raw_bytes":
            out["raw"] = s.get("value", 0)
        elif s.get("name") == "serving.prefix_spill_blob_bytes":
            out["blob"] = s.get("value", 0)
    return out


def _tier_arm(model, prefixes, prompts, tier_quant):
    """One tiered churn run; returns hit rate, outputs, spill byte
    deltas, and the zero-leak audit evidence."""
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    before = _spill_counters()
    bt = PagedContinuousBatcher(
        model, max_batch=MAX_BATCH, s_max=S_MAX, block_size=BLOCK_SIZE,
        n_pages=N_PAGES, compile=False, policy="ondemand",
        prefix_cache=True, host_kv_gib=HOST_GIB, tier_quant=tier_quant)
    try:
        for pre in prefixes:
            bt.submit(pre, NEW_TOKENS)
        bt.run_until_done(max_steps=60000)
        base = bt.prefix_cache.stats()
        rids = [bt.submit(p, NEW_TOKENS) for p in prompts]
        res = bt.run_until_done(max_steps=60000)
        outs = [res[r] for r in rids]
        st = bt.prefix_cache.stats()
        bt.audit_pages()                  # raises on any leak
        rep = bt.prefix_cache.audit_tiers()
        after = _spill_counters()
        hit = st["hit_tokens"] - base["hit_tokens"]
        miss = st["miss_tokens"] - base["miss_tokens"]
        return {
            "hit_rate": round(hit / max(hit + miss, 1), 4),
            "outs": outs,
            "host_bytes": int(rep.get("host_bytes", 0)),
            "spill_raw": int(after["raw"] - before["raw"]),
            "spill_blob": int(after["blob"] - before["blob"]),
            "promotions": int(st["promotions"]),
            "promotion_failures": int(st["promotion_failures"]),
        }
    finally:
        bt.close()


def _weights_arm(model, cfg, quantize):
    """One decode run; returns tokens/s, TPOT p50, and the outputs."""
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    if quantize:
        from paddle_tpu.quantization import serving_quantize
        model = serving_quantize(model)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (20,))
               for _ in range(DECODE_PROMPTS)]
    bt = PagedContinuousBatcher(model, max_batch=MAX_BATCH, s_max=64,
                                block_size=BLOCK_SIZE, compile=True)
    try:
        # warmup: pay the jit traces before the timed window so the
        # arms compare steady-state decode, not compile time
        bt.submit(prompts[0], 2)
        bt.run_until_done(max_steps=9000)
        # best-of-2 repetitions: sub-2ms CPU-proxy steps carry enough
        # scheduler jitter to swamp a few-percent effect; min() is the
        # standard denoiser (outs are deterministic, identical each rep)
        best_rate, best_p50, outs = 0.0, float("inf"), None
        for _ in range(2):
            rids = [bt.submit(p, DECODE_NEW) for p in prompts]
            step_times = []
            t0 = time.perf_counter()
            results = {}
            steps = 0
            while bt._has_work():
                s0 = time.perf_counter()
                for rid in bt.step():
                    results[rid] = bt.pop_result(rid)
                step_times.append(time.perf_counter() - s0)
                steps += 1
                if steps > 60000:
                    raise RuntimeError("decode arm did not drain")
            wall = time.perf_counter() - t0
            outs = [results[r] for r in rids]
            times = np.sort(np.asarray(step_times))
            new_tokens = DECODE_PROMPTS * DECODE_NEW
            best_rate = max(best_rate, new_tokens / max(wall, 1e-9))
            best_p50 = min(best_p50, float(times[len(times) // 2]))
        report = (getattr(model, "_serving_quant_report", None)
                  if quantize else None)
        return {
            "tokens_per_s": round(best_rate, 2),
            "tpot_p50_ms": round(best_p50 * 1e3, 3),
            "outs": outs,
            "quant_report": (
                {"layers_quantized": report["layers_quantized"],
                 "layers_fallback": report["layers_fallback"],
                 "bytes_saved": report["bytes_saved"]}
                if report else None),
        }
    finally:
        bt.close()


def _round_path():
    import glob
    import re
    rounds = []
    for p in glob.glob(os.path.join(_REPO_DIR, "BENCH_QUANT_r*.json")):
        m = re.search(r"BENCH_QUANT_r(\d+)\.json$", os.path.basename(p))
        if m:
            rounds.append(int(m.group(1)))
    n = (max(rounds) + 1) if rounds else 0
    return os.path.join(_REPO_DIR, f"BENCH_QUANT_r{n:02d}.json")


def main():
    on_tpu = False
    try:
        import jax
        on_tpu = jax.devices()[0].platform == "tpu"
    except Exception:
        pass

    # -- tier-capacity arm (random-init model is fine: both runs share
    #    it, and the comparison is fp-blob vs int8-blob storage) -------
    model, cfg = _model(train=False)
    prefixes, prompts = _churn_workload(cfg.vocab_size)
    with paddle.no_grad():
        fp_tier = _tier_arm(model, prefixes, prompts, tier_quant=None)
        q_tier = _tier_arm(model, prefixes, prompts, tier_quant="int8")
    pfx = BLOCK_SIZE * PREFIX_BLOCKS
    tier_match = float(np.mean(
        [np.mean(a[pfx:] == b[pfx:])
         for a, b in zip(fp_tier["outs"], q_tier["outs"])]))
    capacity_ratio = round(
        q_tier["spill_raw"] / max(q_tier["spill_blob"], 1), 2)

    # -- int8-weights arm (sharpened model: measure the quantizer, not
    #    random-logit argmax ties) -------------------------------------
    tmodel, tcfg = _model(train=True)
    with paddle.no_grad():
        fp_dec = _weights_arm(tmodel, tcfg, quantize=False)
        q_dec = _weights_arm(tmodel, tcfg, quantize=True)
    token_match = float(np.mean(
        [np.mean(a[20:] == b[20:])
         for a, b in zip(fp_dec["outs"], q_dec["outs"])]))

    detail = {
        "tpu": on_tpu,
        # tier arm
        "tier_capacity_ratio": capacity_ratio,
        "tier_hit_rate_fp": fp_tier["hit_rate"],
        "tier_hit_rate_int8": q_tier["hit_rate"],
        "tier_host_bytes_fp": fp_tier["host_bytes"],
        "tier_host_bytes_int8": q_tier["host_bytes"],
        "tier_spill_raw_bytes": q_tier["spill_raw"],
        "tier_spill_blob_bytes": q_tier["spill_blob"],
        "tier_token_match_rate": round(tier_match, 4),
        "tier_promotions": q_tier["promotions"],
        "tier_promotion_failures": q_tier["promotion_failures"],
        # weights arm
        "tokens_per_s_fp": fp_dec["tokens_per_s"],
        "tokens_per_s_int8": q_dec["tokens_per_s"],
        "tpot_p50_ms_fp": fp_dec["tpot_p50_ms"],
        "tpot_p50_ms_int8": q_dec["tpot_p50_ms"],
        # CPU-proxy honesty: the int8 arm re-converts every weight each
        # step (XLA:CPU has no int8 matmul), a ~1/batch-fraction FLOP
        # tax with no bandwidth to win back at this scale — the HBM win
        # this arm exists for is a TPU effect, not measured yet
        "tpot_penalty_frac": round(
            q_dec["tpot_p50_ms"] / max(fp_dec["tpot_p50_ms"], 1e-9) - 1,
            4),
        "token_match_rate": round(token_match, 4),
        "quant_report": q_dec["quant_report"],
        "audit_clean": True,       # the tier arms raised otherwise
    }
    line = {
        "metric": "quant_serving_decode_tokens_per_sec",
        "value": q_dec["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "detail": detail,
    }
    try:
        with open(_round_path(), "w") as f:
            json.dump(line, f, indent=1)
            f.write("\n")
    except OSError:
        pass  # artifact write must never sink the bench number
    print(json.dumps(line))


if __name__ == "__main__":
    main()
