"""Continuous batching over the PAGED (block) KV cache.

Reference serving loop analog: block_multihead_attention + request
scheduling (incubate/nn/functional/block_multihead_attention.py:19).
Exactness bar: every request's output equals its single-request
generate_paged()/generate() result regardless of arrival order, slot
reuse, page-pool pressure, or preemption.

Known flake (rare, CPU-backend-only): under heavy host load, compiled
serving paths have intermittently produced a LATE token differing from
the eager/reference path (observed across several test files, including
runs that predate the chunked features). The repeated controlled
runs point at load-dependent partial-sum ordering in the CPU backend's
threaded matmuls flipping argmax near-ties on these tiny random-weight
vocabularies — not at the serving logic, which is bitwise-deterministic
in its host scheduling. The single-executable asserts print their cache
keys on failure so a signature-drift recurrence is diagnosable.
"""
import inspect
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM

from greedy_ref import greedy_ref


def _model(vocab_size=128):
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=vocab_size, hidden_size=64,
                     num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


_ref = greedy_ref


# session-wide retry accounting: one or two load flips across a whole
# heavy parallel run are the documented CPU symptom; MORE than that in
# one session is evidence of a real nondeterminism/scheduling bug that
# retries must not paper over (ADVICE r3).
_RETRY_BUDGET = [3]


def _retry_load_flake(body, attempts=2):
    """Run an exact-token scenario up to `attempts` times (see the module
    docstring: heavy host load can flip argmax near-ties in the CPU
    backend's threaded matmuls — a CPU-ONLY symptom). A LOGIC regression
    fails every attempt and still fails the test; a load flip passes the
    retry — but LOUDLY, debited from a small per-session budget.

    Gating (VERDICT r3 #9): on TPU the same scenarios must be exact on
    the first try, so the helper never retries there; setting
    PADDLE_EXACT_STRICT=1 disables retries everywhere (CI strict mode).
    """
    import os
    import warnings

    import jax
    if (os.environ.get("PADDLE_EXACT_STRICT") == "1"
            or jax.devices()[0].platform == "tpu"):
        attempts = 1
    for i in range(attempts):
        try:
            body()
            return
        except AssertionError as e:
            if i + 1 == attempts:
                raise
            if _RETRY_BUDGET[0] <= 0:
                raise AssertionError(
                    "exact-token retry budget exhausted this session — "
                    "this is no longer the rare CPU load flake; "
                    "investigate as a real bug") from e
            _RETRY_BUDGET[0] -= 1
            warnings.warn(
                f"exact-token attempt {i + 1} failed and was retried "
                f"(documented CPU load flake; {_RETRY_BUDGET[0]} session "
                f"retries left): {str(e)[:300]}")


@pytest.mark.parametrize("with_eos", [False, True], ids=["budget", "eos"])
def test_paged_batch_matches_solo_generate(with_eos):
    """Every request equals its solo decode. ``eos``: a token the first
    request generates in the middle of its answer is the end-of-sequence
    id, so each request ends at the step that token first appears (the
    token kept, nothing after it), or at its budget."""
    m = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 12, 7)]
    ns = [6, 4, 8, 5]
    refs = [_ref(m, p, n) for p, n in zip(prompts, ns)]
    eos = None
    if with_eos:
        answer = refs[0][len(prompts[0]):].tolist()
        eos = next(t for i, t in enumerate(answer)
                   if 0 < i < ns[0] - 1 and t not in answer[:i])
        for i, (p, full) in enumerate(zip(prompts, refs)):
            hits = np.flatnonzero(full[len(p):] == eos)
            if len(hits):
                refs[i] = full[:len(p) + hits[0] + 1]
        assert len(prompts[0]) + 1 < len(refs[0]) < len(prompts[0]) + ns[0]
    b = PagedContinuousBatcher(m, max_batch=4, s_max=32, block_size=8,
                               eos_id=eos, compile=False)
    rids = [b.submit(p, n) for p, n in zip(prompts, ns)]
    outs = b.run_until_done()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(outs[rid], ref,
                                      err_msg=f"request {rid}")
    assert b.stats()["generated_tokens"] == sum(
        len(r) - len(p) for r, p in zip(refs, prompts))
    # every page returned to the pool after the run
    assert b.free_page_count == b.n_pages
    assert (b._bt == b._scratch).all()


def test_paged_slot_and_page_reuse():
    """More requests than slots: later arrivals admit into freed slots and
    recycled pages mid-run, still token-exact."""
    m = _model()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, (s,)) for s in (4, 6, 8, 5, 7, 9)]
    ns = [3, 7, 4, 6, 5, 4]
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False)
    rids = [b.submit(p, n) for p, n in zip(prompts[:3], ns[:3])]
    for _ in range(3):
        b.step()
    rids += [b.submit(p, n) for p, n in zip(prompts[3:], ns[3:])]
    outs = b.run_until_done()
    # earlier finishers were popped by the first steps' bookkeeping only
    # if finished; collect any remaining
    for rid, p, n in zip(rids, prompts, ns):
        got = outs.get(rid)
        if got is None:
            got = b.pop_result(rid)
        np.testing.assert_array_equal(got, _ref(m, p, n),
                                      err_msg=f"request {rid}")
    assert b.free_page_count == b.n_pages


def test_ondemand_growth_allocates_lazily():
    """ondemand admits with only the prompt's pages and grows across block
    boundaries; outputs stay exact and the pool drains/refills."""
    m = _model()
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 128, (5,))
    n = 14  # crosses two block_size=8 boundaries from row 5
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               policy="ondemand", compile=False)
    rid = b.submit(prompt, n)
    b.step()
    used_after_admit = b.n_pages - b.free_page_count
    assert used_after_admit == 1  # ceil((5+1)/8) pages only, not worst case
    outs = b.run_until_done()
    np.testing.assert_array_equal(outs[rid], _ref(m, prompt, n))
    assert b.free_page_count == b.n_pages


@pytest.mark.parametrize("compile", [False, True],
                         ids=["eager", "compiled"])
def test_ondemand_preemption_is_exact(compile):
    """Pool too small for both requests' full lengths: the later request
    must be preempted (pages freed, re-queued) and still finish with
    exactly its solo continuation (recompute-on-resume)."""
    m = _model()
    rng = np.random.RandomState(3)
    p0 = rng.randint(0, 128, (6,))
    p1 = rng.randint(0, 128, (6,))

    def body():
        # block_size 4, 6 pages total: each request needs up to
        # ceil((6+10)/4) = 4 pages; both can admit (2+2) but can't both grow
        b = PagedContinuousBatcher(m, max_batch=2, s_max=24, block_size=4,
                                   n_pages=6, policy="ondemand",
                                   compile=compile)
        r0 = b.submit(p0, 10)
        r1 = b.submit(p1, 10)
        preempted = False
        for _ in range(100):
            before_pending = len(b._pending)
            b.step()
            if len(b._pending) > before_pending:
                preempted = True
            if not b._pending and not b._slot_req:
                break
        outs = {r0: b.pop_result(r0), r1: b.pop_result(r1)}
        assert preempted, "pool pressure should have forced a preemption"
        np.testing.assert_array_equal(outs[r0], _ref(m, p0, 10))
        np.testing.assert_array_equal(outs[r1], _ref(m, p1, 10))
        assert b.free_page_count == b.n_pages
        assert b.audit_pages() == 0

    _retry_load_flake(body, attempts=2 if compile else 1)


@pytest.mark.smoke
def test_compiled_paged_batcher_matches_eager():
    # the ONE compiled-serving exactness test kept in the smoke tier
    # (the heavier chunked compiled tests run in the full suite)
    m = _model()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 7)]
    ns = [6, 4, 5]

    def body():
        be = PagedContinuousBatcher(m, max_batch=4, s_max=32, block_size=8,
                                    compile=False)
        bc = PagedContinuousBatcher(m, max_batch=4, s_max=32, block_size=8,
                                    compile=True)
        re_ = [be.submit(p, n) for p, n in zip(prompts, ns)]
        rc = [bc.submit(p, n) for p, n in zip(prompts, ns)]
        oe = be.run_until_done()
        oc = bc.run_until_done()
        for a, b_ in zip(re_, rc):
            np.testing.assert_array_equal(oe[a], oc[b_])
        # one decode executable across every step/occupancy (the state's
        # static ints must survive the compiled-call round trip)
        assert len(bc._step_fn._cache) == 1

    _retry_load_flake(body)


def test_paged_capacity_errors():
    m = _model()
    b = PagedContinuousBatcher(m, max_batch=2, s_max=16, block_size=8,
                               compile=False)
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        b.submit(np.zeros(10, np.int64), 8)
    small = PagedContinuousBatcher(m, max_batch=1, s_max=16, block_size=8,
                                   n_pages=1, compile=False)
    with pytest.raises(ValueError, match="pool"):
        small.submit(np.zeros(6, np.int64), 8)
    # admission always emits one token, so zero-token requests can't
    # honor the exactness-vs-generate contract and must be rejected
    with pytest.raises(ValueError, match="max_new_tokens"):
        b.submit(np.zeros(4, np.int64), 0)


def _llama():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config(vocab_size=128))
    m.eval()
    return m


@pytest.mark.smoke
def test_llama_paged_generate_matches_dense():
    """GQA paged route (block_gqa_attention: unexpanded kv heads, RoPE at
    timeline positions) reproduces the dense-cache decode exactly,
    including across page boundaries."""
    m = _llama()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 7)).astype(np.int64))
    with paddle.no_grad():
        dense = m.generate(ids, max_new_tokens=8).numpy()
        paged = m.generate_paged(ids, max_new_tokens=8,
                                 block_size=4).numpy()
    np.testing.assert_array_equal(dense, paged)


@pytest.mark.parametrize("compile", [False, True],
                         ids=["eager", "compiled"])
def test_llama_paged_batcher_token_exact(compile):
    """The SAME PagedContinuousBatcher (model-agnostic paged-state
    protocol) serves the GQA flagship, preemption included; compiled, its
    decode step (GQA + RoPE through the block cache) is one executable."""
    m = _llama()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 12)]
    ns = [6, 8, 5]

    def body():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=4,
                                   n_pages=10, policy="ondemand",
                                   compile=compile)
        rids = [b.submit(p, n) for p, n in zip(prompts, ns)]
        outs = b.run_until_done()
        for rid, p, n in zip(rids, prompts, ns):
            np.testing.assert_array_equal(outs[rid], _ref(m, p, n),
                                          err_msg=f"request {rid}")
        assert b.free_page_count == b.n_pages
        if compile:
            assert len(b._step_fn._cache) == 1, list(b._step_fn._cache)

    _retry_load_flake(body, attempts=2 if compile else 1)


def test_llama_compiled_paged_step_matches_eager():
    from paddle_tpu import jit
    m = _llama()
    rng = np.random.RandomState(2)
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 6)).astype(np.int64))
    with paddle.no_grad():
        ref = m.generate_paged(ids, max_new_tokens=6, block_size=4).numpy()
        step = jit.to_static(m.paged_decode_step)
        out = m.generate_paged(ids, max_new_tokens=6, block_size=4,
                               decode_fn=step).numpy()
    np.testing.assert_array_equal(ref, out)


def test_sampled_paged_batching_runs():
    """Sampling through the paged batcher: shapes/lifecycle sane (exact
    match vs solo is not defined across interleavings of one shared rng)."""
    m = _model()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 7)]
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False, do_sample=True,
                               temperature=0.8, top_k=20, seed=0)
    rids = [b.submit(p, 6) for p in prompts]
    outs = b.run_until_done()
    for rid, p in zip(rids, prompts):
        assert outs[rid].shape == (len(p) + 6,)
    assert b.free_page_count == b.n_pages


def test_batcher_stats():
    """Serving observability: counters reflect steps, tokens, occupancy,
    completions, and preemptions."""
    m = _model()
    rng = np.random.RandomState(6)
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False)
    rids = [b.submit(rng.randint(0, 128, (5,)), 4) for _ in range(2)]
    b.run_until_done()
    s = b.stats()
    assert s["completed_requests"] == 2
    assert s["generated_tokens"] == 8          # 2 requests x 4 tokens
    assert s["steps"] == 3                     # admission tok + 3 decode steps
    assert s["mean_active_slots"] == 2.0
    assert s["slot_utilization"] == 1.0
    assert s["tokens_per_sec"] > 0
    assert s["pending_now"] == 0 and s["active_now"] == 0


def _decode_launches(engine="paged"):
    from paddle_tpu.observability.metrics import get_registry
    family = get_registry().get("serving_decode_attention_launches_total")
    return {path: family.labels(engine=engine, path=path).value
            for path in ("kernel", "gather")}


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_decode_attention_launches_counted_by_path(family):
    """``serving_decode_attention_launches_total{path}`` counts one a
    launch of the decode executable, under the route its attention was
    built with: off the chip that is ``gather`` for every family (on the
    chip ``kernel`` for the Llama family over an unquantized pool), and
    ``stats()`` carries the same word."""
    m = _llama() if family == "llama" else _model()
    rng = np.random.RandomState(7)
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False)
    assert b.stats()["decode_attention_path"] == "gather"
    before = _decode_launches()
    for _ in range(2):
        b.submit(rng.randint(0, 128, (5,)), 6)
    b.run_until_done()
    s = b.stats()
    after = _decode_launches()
    assert after["kernel"] == before["kernel"]
    # one launch a step
    assert after["gather"] - before["gather"] == s["steps"] > 0


def test_decode_attention_path_is_the_models_word(monkeypatch):
    """The batcher does not decide the route: it asks the model that
    builds the executable, with the pool it allocated."""
    m = _llama()
    seen = []

    def route(pool):
        seen.append(tuple(pool[0][0].shape))
        return "kernel"
    monkeypatch.setattr(m, "paged_decode_attention_path", route,
                        raising=False)
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False)
    assert b.stats()["decode_attention_path"] == "kernel"
    assert seen == [(b.n_pages + 1, m.config.num_key_value_heads, 8,
                     m.config.head_dim)]
    before = _decode_launches()
    b.submit(np.arange(5), 3)
    b.run_until_done()
    after = _decode_launches()
    assert after["kernel"] - before["kernel"] == b.stats()["steps"]
    assert after["gather"] == before["gather"]


# -- what the constructor refuses: one case a ``raise ValueError`` ---------

def _calibrated():
    m = _model()
    m.calibrate_cachekv_int8(paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (2, 12)).astype(np.int64)))
    return m


def _refusing():
    m = _model()
    m.paged_serving_contract = lambda: {"unsupported": {
        "prefix_cache": "its cache is more than pages"}}
    return m


_MODELS = {"plain": _model, "calibrated": _calibrated,
           "refusing": _refusing, "vocab_96": lambda: _model(96)}

# (the model, the options, a fragment of the message), in the constructor's
# order; ``draft_model`` names a model of ``_MODELS`` too
REFUSED = {
    "policy": ("plain", dict(policy="lazy"), "unknown policy 'lazy'"),
    "contract": ("refusing", dict(prefix_cache=True),
                 "prefix_cache is not supported for GPT2ForCausalLM: its "
                 "cache is more than pages"),
    "promo_slots": ("plain", dict(promo_slots=0),
                    "promo_slots must be >= 1"),
    "promo_chunk_blocks": ("plain", dict(promo_chunk_blocks=0),
                           "promo_chunk_blocks must be >= 1"),
    "prefix_cache+cache_quant": (
        "plain", dict(prefix_cache=True, cache_quant="dynamic_int8"),
        "prefix_cache shares pages across requests"),
    "draft_model+do_sample": (
        "plain", dict(draft_model="plain", do_sample=True), "greedy-only"),
    "draft_model+cache_quant": (
        "plain", dict(draft_model="plain", cache_quant="dynamic_int8"),
        "draft_model is not supported with dynamic cachekv quant"),
    "draft_model+prefill_chunk": (
        "plain", dict(draft_model="plain", prefill_chunk=8),
        "draft_model is not supported with prefill_chunk"),
    "draft_k": ("plain", dict(draft_model="plain", draft_k=0),
                "draft_k must be >= 1"),
    "draft_vocab": ("plain", dict(draft_model="vocab_96"),
                    "draft vocab 96 != target vocab 128"),
    "prefill_chunk<1": ("plain", dict(prefill_chunk=0),
                        "prefill_chunk must be >= 1"),
    "cache_quant": ("plain", dict(cache_quant="int4"),
                    "unknown cache_quant 'int4'"),
    "kv_quant": ("plain", dict(kv_quant="int4"), "unknown kv_quant 'int4'"),
    "kv_quant+cache_quant": (
        "plain", dict(kv_quant="int8", cache_quant="dynamic_int8"),
        "two quantizers for the same pool; pick one"),
    "kv_quant_uncalibrated": ("plain", dict(kv_quant="int8"),
                              "run model.calibrate_cachekv_int8"),
    "kv_quant+draft_model": (
        "calibrated", dict(kv_quant="int8", draft_model="plain"),
        "kv_quant is not supported with draft_model"),
    "tier_quant": ("plain", dict(tier_quant="fp8"),
                   "unknown tier_quant 'fp8'"),
    "tier_quant_alone": ("plain", dict(tier_quant="int8"),
                         "it needs prefix_cache=True"),
    "tier_quant_calibrated": (
        "calibrated",
        dict(tier_quant="int8", prefix_cache=True, host_kv_gib=0.01),
        "tier_quant is redundant with calibrated int8 pages"),
    "cache_quant+prefill_chunk_1": (
        "plain", dict(cache_quant="dynamic_int8", prefill_chunk=1),
        "needs prefill_chunk >= 2"),
    "prefill_chunk>s_max": ("plain", dict(prefill_chunk=64),
                            "prefill_chunk=64 exceeds s_max=32"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_batcher_refuses(case):
    """The option matrix that is left: what ``PagedContinuousBatcher``
    will not be built with, one case a guard."""
    model, options, message = REFUSED[case]
    if "draft_model" in options:
        options = dict(options,
                       draft_model=_MODELS[options["draft_model"]]())
    with pytest.raises(ValueError, match=re.escape(message)):
        PagedContinuousBatcher(_MODELS[model](), max_batch=2, s_max=32,
                               block_size=8, compile=False, **options)


def test_batcher_refuses_has_a_case_a_guard():
    source = inspect.getsource(PagedContinuousBatcher.__init__)
    assert source.count("raise ValueError") == len(REFUSED)
