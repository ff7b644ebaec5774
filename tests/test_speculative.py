"""Greedy speculative decoding over the paged cache.

Exactness bar: generate_paged_speculative(target, draft, ...) must equal
target.generate(...) token for token, for ANY draft — a good draft only
changes how many target dispatches that takes, never the output. This is
the defining property of greedy draft/verify decoding and what makes the
feature safe to enable by default in serving.

Beyond-reference feature (the reference snapshot has no in-tree
speculative decoding); the paged cache makes rejection rollback free —
host-owned dec_lens bounds every read, stale rows are overwritten on the
next append (see GPT2ForCausalLM._speculative_loop).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config

from greedy_ref import greedy_ref
from test_paged_batching import _retry_load_flake


def _gpt(seed, layers=2, hidden=64):
    paddle.seed(seed)
    cfg = GPT2Config(vocab_size=128, hidden_size=hidden,
                     num_hidden_layers=layers, num_attention_heads=4,
                     max_position_embeddings=96, dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


def _llama(seed):
    paddle.seed(seed)
    m = LlamaForCausalLM(llama_tiny_config(max_position_embeddings=96))
    m.eval()
    return m


_ref = greedy_ref


def test_speculative_matches_greedy_any_draft():
    """Output == target greedy regardless of the draft: a same-family
    smaller draft, an unrelated (different-seed) draft, and the target
    itself as its own draft (always-accept path)."""
    _retry_load_flake(_any_draft_body, attempts=3)


def _any_draft_body():
    target = _gpt(0)
    rng = np.random.RandomState(50)
    prompt = rng.randint(0, 128, (11,))
    want = _ref(target, prompt, 14)
    ids = paddle.to_tensor(np.asarray(prompt, np.int64)[None])
    for draft in (_gpt(1, layers=1, hidden=32), _gpt(7), target):
        out, st = target.generate_paged_speculative(
            ids, 14, draft, draft_k=4, block_size=8, return_stats=True)
        np.testing.assert_array_equal(out.numpy()[0], want)
        assert st["rounds"] > 0
    # the self-draft must accept every proposal (it IS the target)
    out, st = target.generate_paged_speculative(
        ids, 14, target, draft_k=4, block_size=8, return_stats=True)
    assert st["acceptance_rate"] == 1.0
    assert st["tokens_per_target_dispatch"] > 1.0


def test_speculative_llama_and_cross_family():
    """Llama target with a Llama draft AND with a GPT-2 draft (both
    families speak the shared paged-state convention)."""
    _retry_load_flake(_cross_family_body, attempts=3)


def _cross_family_body():
    target = _llama(0)
    rng = np.random.RandomState(51)
    prompt = rng.randint(0, 128, (9,))
    want = _ref(target, prompt, 12)
    ids = paddle.to_tensor(np.asarray(prompt, np.int64)[None])
    for draft in (_llama(3), _gpt(4)):
        out = target.generate_paged_speculative(ids, 12, draft,
                                                draft_k=3, block_size=8)
        np.testing.assert_array_equal(out.numpy()[0], want)


def test_speculative_eos_and_budget_edges():
    _retry_load_flake(_edges_body, attempts=3)


def _edges_body():
    target = _gpt(0)
    draft = _gpt(2, layers=1, hidden=32)
    rng = np.random.RandomState(52)
    prompt = rng.randint(0, 128, (10,))
    ids = paddle.to_tensor(np.asarray(prompt, np.int64)[None])
    full = _ref(target, prompt, 12)
    gen = full[len(prompt):]
    # force an EOS mid-generation: output truncates exactly there
    eos = int(gen[4])
    out = target.generate_paged_speculative(ids, 12, draft, draft_k=4,
                                            block_size=8, eos_id=eos)
    np.testing.assert_array_equal(out.numpy()[0], full[:len(prompt) + 5])
    # max_new_tokens == 1: no draft round at all, still exact
    out1 = target.generate_paged_speculative(ids, 1, draft, draft_k=4,
                                             block_size=8)
    np.testing.assert_array_equal(out1.numpy()[0], _ref(target, prompt, 1))
    # max_new_tokens == 0 returns the prompt unchanged, like generate()
    out0 = target.generate_paged_speculative(ids, 0, draft, draft_k=4,
                                             block_size=8)
    np.testing.assert_array_equal(out0.numpy()[0], prompt)
    # budget not a multiple of draft_k: the tail rounds shrink k
    out2 = target.generate_paged_speculative(ids, 6, draft, draft_k=4,
                                             block_size=8)
    np.testing.assert_array_equal(out2.numpy()[0], _ref(target, prompt, 6))


def test_speculative_guards():
    target = _gpt(0)
    ids = paddle.to_tensor(np.zeros((1, 8), np.int64))
    with pytest.raises(ValueError, match="draft_k"):
        target.generate_paged_speculative(ids, 4, target, draft_k=0)
    with pytest.raises(ValueError, match="single-sequence"):
        target.generate_paged_speculative(
            paddle.to_tensor(np.zeros((2, 8), np.int64)), 4, target)
    paddle.seed(9)
    other = GPT2ForCausalLM(GPT2Config(vocab_size=64, hidden_size=32,
                                       num_hidden_layers=1,
                                       num_attention_heads=2,
                                       max_position_embeddings=64,
                                       dropout=0.0))
    with pytest.raises(ValueError, match="vocab"):
        target.generate_paged_speculative(ids, 4, other)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        target.generate_paged_speculative(
            paddle.to_tensor(np.zeros((1, 90), np.int64)), 20, target)
