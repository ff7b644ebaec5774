"""Continuous batching over the compiled KV-cache decode step.

Reference serving loop analog (AnalysisPredictor + request scheduling);
the TPU design point is ONE static-shape decode executable + host-side
slot admission/eviction. Exactness bar: every request's output equals the
single-request generate() result, regardless of arrival order or slot
reuse.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatcher
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM

from greedy_ref import greedy_ref


def _model():
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


_ref = greedy_ref


def test_batched_requests_match_single_generate():
    m = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 12, 7)]
    ns = [6, 4, 8, 5]
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=4, s_max=32, compile=False)
        rids = [b.submit(p, n) for p, n in zip(prompts, ns)]
        outs = b.run_until_done()
    for rid, p, n in zip(rids, prompts, ns):
        np.testing.assert_array_equal(outs[rid], _ref(m, p, n),
                                      err_msg=f"request {rid}")


def test_staggered_arrival_and_slot_reuse():
    """More requests than slots: later arrivals admit into freed slots
    mid-run and still match their solo decode exactly."""
    m = _model()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, (s,)) for s in (4, 6, 8, 5, 7, 9)]
    ns = [3, 7, 4, 6, 5, 4]
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        rids = [b.submit(p, n) for p, n in zip(prompts[:3], ns[:3])]
        early = []
        for _ in range(3):
            early += b.step()
        # new work arrives while the batch is mid-flight
        rids += [b.submit(p, n) for p, n in zip(prompts[3:], ns[3:])]
        outs = b.run_until_done()
        for rid in early:  # manual-step finishes are popped explicitly
            outs[rid] = b.pop_result(rid)
    assert b.active == 0
    for rid, p, n in zip(rids, prompts, ns):
        np.testing.assert_array_equal(outs[rid], _ref(m, p, n),
                                      err_msg=f"request {rid}")


def test_compiled_step_matches_eager_batcher():
    m = _model()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 11)]
    with paddle.no_grad():
        b1 = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        for p in prompts:
            b1.submit(p, 5)
        ref = b1.run_until_done()
        b2 = ContinuousBatcher(m, max_batch=2, s_max=32, compile=True)
        rids = [b2.submit(p, 5) for p in prompts]
        outs = b2.run_until_done()
    for rid in rids:
        np.testing.assert_array_equal(outs[rid], ref[rid])


def test_eos_early_stop():
    m = _model()
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 128, (6,))
    ref = _ref(m, prompt, 10)
    gen = ref[6:]
    # pick the 3rd generated token as "EOS": the batcher must stop there
    eos = int(gen[2])
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=2, s_max=32, eos_id=eos,
                              compile=False)
        rid = b.submit(prompt, 10)
        outs = b.run_until_done()
    got = outs[rid]
    assert len(got) <= len(ref)
    assert int(got[-1]) == eos
    np.testing.assert_array_equal(got, ref[:len(got)])


def test_capacity_validation():
    m = _model()
    b = ContinuousBatcher(m, max_batch=1, s_max=16, compile=False)
    with pytest.raises(ValueError, match="capacity"):
        b.submit(np.arange(10), 10)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        ContinuousBatcher(m, max_batch=1, s_max=128, compile=False)


def test_step_reports_admission_finishes_and_results_pop():
    """Review regressions: a request finishing AT admission must be
    reported by that step() call; run_until_done pops its run's results
    so a reused batcher neither leaks nor re-reports stale rids."""
    m = _model()
    rng = np.random.RandomState(4)
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        rid1 = b.submit(rng.randint(0, 128, (5,)), 1)  # finishes at admit
        done = b.step()
        assert rid1 in done
        # idle batcher: step() reports nothing (not historical finishes)
        assert b.step() == []
        out1 = b.pop_result(rid1)
        assert len(out1) == 6
        with pytest.raises(KeyError):
            b.result(rid1)
        # a second run returns ONLY its own rids
        rid2 = b.submit(rng.randint(0, 128, (4,)), 3)
        outs = b.run_until_done()
        assert set(outs) == {rid2}


def test_run_until_done_budget_raises():
    m = _model()
    rng = np.random.RandomState(5)
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=1, s_max=32, compile=False)
        for _ in range(3):
            b.submit(rng.randint(0, 128, (4,)), 4)
        with pytest.raises(RuntimeError, match="remain after"):
            b.run_until_done(max_steps=2)


def test_sampled_batching_is_seeded_and_diverse():
    """do_sample in the batcher: reproducible under a seed; differs from
    greedy at temperature 1."""
    m = _model()
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 128, (6,)) for _ in range(2)]
    with paddle.no_grad():
        def run(seed):
            b = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False,
                                  do_sample=True, temperature=1.0,
                                  seed=seed)
            rids = [b.submit(p, 6) for p in prompts]
            outs = b.run_until_done()
            return [outs[r].tolist() for r in rids]

        s1, s2, s3 = run(7), run(7), run(8)
        g = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        rids = [g.submit(p, 6) for p in prompts]
        gouts = g.run_until_done()
        greedy = [gouts[r].tolist() for r in rids]
    assert s1 == s2
    assert s1 != s3
    assert s1 != greedy


def test_batcher_serves_llama():
    """The batcher is model-agnostic: the GQA flagship serves through the
    same slots, token-exact vs its solo generate."""
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
    paddle.seed(0)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            num_attention_heads=4, num_key_value_heads=2,
                            vocab_size=128, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 12)]
    with paddle.no_grad():
        b = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        rids = [b.submit(p, 5) for p in prompts]
        outs = b.run_until_done()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _ref(m, p, 5))
