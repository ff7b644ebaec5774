"""Continuous batching over the PAGED (block) KV cache.

Reference serving loop analog: block_multihead_attention + request
scheduling (incubate/nn/functional/block_multihead_attention.py:19).
Exactness bar: every request's output equals its single-request
generate_paged()/generate() result regardless of arrival order, slot
reuse, page-pool pressure, or preemption.

Known flake (rare, CPU-backend-only): under heavy host load, compiled
serving paths have intermittently produced a LATE token differing from
the eager/reference path (observed across several test files, including
runs that predate the fused/chunked features). The repeated controlled
runs point at load-dependent partial-sum ordering in the CPU backend's
threaded matmuls flipping argmax near-ties on these tiny random-weight
vocabularies — not at the serving logic, which is bitwise-deterministic
in its host scheduling. The single-executable asserts print their cache
keys on failure so a signature-drift recurrence is diagnosable.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM


def _model():
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


def _ref(m, prompt, n):
    ids = paddle.to_tensor(np.asarray(prompt, np.int64)[None, :])
    with paddle.no_grad():
        return m.generate(ids, max_new_tokens=n).numpy()[0]


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


def test_paged_batch_matches_solo_generate():
    m = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 12, 7)]
    ns = [6, 4, 8, 5]
    b = PagedContinuousBatcher(m, max_batch=4, s_max=32, block_size=8,
                               compile=False)
    rids = [b.submit(p, n) for p, n in zip(prompts, ns)]
    outs = b.run_until_done()
    for rid, p, n in zip(rids, prompts, ns):
        np.testing.assert_array_equal(outs[rid], _ref(m, p, n),
                                      err_msg=f"request {rid}")
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


def test_ondemand_preemption_is_exact():
    """Pool too small for both requests' full lengths: the later request
    must be preempted (pages freed, re-queued) and still finish with
    exactly its solo continuation (recompute-on-resume)."""
    m = _model()
    rng = np.random.RandomState(3)
    p0 = rng.randint(0, 128, (6,))
    p1 = rng.randint(0, 128, (6,))
    # block_size 4, 6 pages total: each request needs up to
    # ceil((6+10)/4) = 4 pages; both can admit (2+2) but can't both grow
    b = PagedContinuousBatcher(m, max_batch=2, s_max=24, block_size=4,
                               n_pages=6, policy="ondemand", compile=False)
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


@pytest.mark.smoke
def test_compiled_paged_batcher_matches_eager():
    # the ONE compiled-serving exactness test kept in the smoke tier
    # (the heavier chunked/fused compiled tests run in the full suite)
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


def test_llama_paged_batcher_token_exact():
    """The SAME PagedContinuousBatcher (model-agnostic paged-state
    protocol) serves the GQA flagship, preemption included."""
    m = _llama()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 12)]
    ns = [6, 8, 5]
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=4,
                               n_pages=10, policy="ondemand",
                               compile=False)
    rids = [b.submit(p, n) for p, n in zip(prompts, ns)]
    outs = b.run_until_done()
    for rid, p, n in zip(rids, prompts, ns):
        ids = paddle.to_tensor(np.asarray(p, np.int64)[None, :])
        with paddle.no_grad():
            ref = m.generate(ids, max_new_tokens=n).numpy()[0]
        np.testing.assert_array_equal(outs[rid], ref,
                                      err_msg=f"request {rid}")
    assert b.free_page_count == b.n_pages


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


@pytest.mark.parametrize("family,decode_block", [
    ("llama", None), ("llama", 4), ("gpt2", None)],
    ids=["llama", "llama_decode_block", "gpt2"])
def test_decode_attention_launches_counted_by_path(family, decode_block):
    """``serving_decode_attention_launches_total{path}`` counts one a
    launch of the decode executable, under the route its attention was
    built with: off the chip that is ``gather`` for every family (on the
    chip ``kernel`` for the Llama family over an unquantized pool), and
    ``stats()`` carries the same word."""
    m = _llama() if family == "llama" else _model()
    rng = np.random.RandomState(7)
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False, decode_block=decode_block)
    assert b.stats()["decode_attention_path"] == "gather"
    before = _decode_launches()
    for _ in range(2):
        b.submit(rng.randint(0, 128, (5,)), 6)
    b.run_until_done()
    s = b.stats()
    after = _decode_launches()
    assert after["kernel"] == before["kernel"]
    # one launch a step on the plain path; a K-step block is one launch
    launches = s["steps"] - (decode_block - 1) * s["decode_blocks"] \
        if decode_block else s["steps"]
    assert launches > 0
    assert after["gather"] - before["gather"] == launches


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


# -- chunked prefill (one executable for every prompt length) --------------

def test_chunked_prefill_token_exact_mixed_lengths():
    """Fixed-width append chunks reproduce the one-shot prefill exactly
    for prompts shorter, equal, and longer than the chunk — including a
    zero-padded tail chunk — for both families."""
    for mk in (_model, _llama):
        m = mk()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 128, (s,)) for s in (3, 8, 13, 17)]
        b = PagedContinuousBatcher(m, max_batch=4, s_max=40, block_size=8,
                                   prefill_chunk=8, compile=False)
        rids = [b.submit(p, 6) for p in prompts]
        outs = b.run_until_done()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _ref(m, p, 6),
                                          err_msg=f"{mk.__name__} {rid}")
        assert b.free_page_count == b.n_pages


def test_chunked_prefill_single_executable():
    """The point of chunking: serving many distinct prompt lengths
    compiles exactly ONE prefill executable (vs one per length on the
    unchunked path)."""
    m = _model()
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, 128, (s,)) for s in (3, 7, 9, 14)]

    def body():
        b = PagedContinuousBatcher(m, max_batch=4, s_max=40, block_size=8,
                                   prefill_chunk=8, compile=True)
        rids = [b.submit(p, 4) for p in prompts]
        outs = b.run_until_done()
        assert len(b._chunk_fn._cache) == 1, \
            list(b._chunk_fn._cache)      # one signature ever
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _ref(m, p, 4))

    _retry_load_flake(body)


def test_chunked_prefill_with_preemption():
    """Chunked admission composes with on-demand growth + preemption
    (resume re-prefills prompt+generated through the chunk path)."""
    m = _model()
    rng = np.random.RandomState(9)
    p0 = rng.randint(0, 128, (6,))
    p1 = rng.randint(0, 128, (6,))
    b = PagedContinuousBatcher(m, max_batch=2, s_max=24, block_size=4,
                               n_pages=6, policy="ondemand",
                               prefill_chunk=4, compile=False)
    r0, r1 = b.submit(p0, 10), b.submit(p1, 10)
    outs = b.run_until_done()
    assert b.stats()["preemptions"] >= 1
    np.testing.assert_array_equal(outs[r0], _ref(m, p0, 10))
    np.testing.assert_array_equal(outs[r1], _ref(m, p1, 10))


def test_chunked_prefill_tail_clamped_to_capacity():
    """Chunk width not aligned to capacity: the tail chunk shortens
    instead of overflowing the block table (review finding)."""
    m = _model()
    rng = np.random.RandomState(10)
    # s_max=40, block_size=8 -> capacity 40; C=16: a 35-token prompt pads
    # to 48 unclamped, which would index a 6th block in a 5-block table
    p = rng.randint(0, 128, (35,))
    b = PagedContinuousBatcher(m, max_batch=1, s_max=40, block_size=8,
                               prefill_chunk=16, compile=False)
    rid = b.submit(p, 5)
    outs = b.run_until_done()
    np.testing.assert_array_equal(outs[rid], _ref(m, p, 5))
    assert b.free_page_count == b.n_pages


# -- fused admission (vLLM unified scheduling) -----------------------------

def test_fused_admission_token_exact_both_families():
    """One fused executable advances all decode slots AND one admission
    chunk per step; every request still matches its solo decode."""
    for mk in (_model, _llama):
        m = mk()
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, 128, (s,)) for s in (5, 11, 17, 8, 22)]
        b = PagedContinuousBatcher(m, max_batch=3, s_max=40, block_size=8,
                                   prefill_chunk=8, fused_admission=True,
                                   compile=False)
        rids = [b.submit(p, 6) for p in prompts]
        outs = b.run_until_done()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _ref(m, p, 6),
                                          err_msg=f"{mk.__name__} {rid}")
        assert b.free_page_count == b.n_pages


def test_fused_admission_single_executable_and_overlap():
    """The fused step is ONE compiled executable at every occupancy and
    prompt length, and decode genuinely progresses while a prompt
    admits (total steps ~ max of the two, not their sum)."""
    m = _model()
    rng = np.random.RandomState(13)
    long_decode = rng.randint(0, 128, (4,))
    long_prompt = rng.randint(0, 128, (32,))   # 4 chunks at C=8

    def body():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=48, block_size=8,
                                   prefill_chunk=8, fused_admission=True,
                                   compile=True)
        r0 = b.submit(long_decode, 12)
        b.step()                               # r0 admitted (4-tok, 1 chunk)
        r1 = b.submit(long_prompt, 4)
        outs = b.run_until_done()
        assert len(b._fused_fn._cache) == 1, list(b._fused_fn._cache)
        np.testing.assert_array_equal(outs[r0], _ref(m, long_decode, 12))
        np.testing.assert_array_equal(outs[r1], _ref(m, long_prompt, 4))
        # overlap: r0's 12 decode steps cover r1's 4 admission chunks —
        # the run fits in far fewer steps than the sequential sum (~13 vs 21)
        assert b.stats()["steps"] <= 16

    _retry_load_flake(body)


def test_fused_admission_guards():
    m = _model()
    with pytest.raises(ValueError, match="fused_admission needs"):
        PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               fused_admission=True, compile=False)
    with pytest.raises(ValueError, match="exceeds s_max"):
        PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               prefill_chunk=64, compile=False)


def test_fused_admission_abort_under_pool_pressure():
    """ondemand + fused: when a live decode needs a page and only the
    in-flight admission holds them, the admission is aborted (requeued,
    pages freed) instead of failing the step — and everything still
    finishes token-exact."""
    m = _model()
    rng = np.random.RandomState(14)
    p0 = rng.randint(0, 128, (4,))
    p1 = rng.randint(0, 128, (13,))
    # 6 pages of 4 rows: p0 admits with 2 pages and must grow to 4;
    # p1's 2-chunk admission reserves 4 — the pool cannot hold both
    # timelines (4 + 5 > 6), forcing preemption/abort mid-run
    b = PagedContinuousBatcher(m, max_batch=2, s_max=24, block_size=4,
                               n_pages=6, policy="ondemand",
                               prefill_chunk=8, fused_admission=True,
                               compile=False)
    r0 = b.submit(p0, 10)
    r1 = b.submit(p1, 4)
    outs = b.run_until_done(max_steps=300)
    assert b.stats()["preemptions"] >= 1
    np.testing.assert_array_equal(outs[r0], _ref(m, p0, 10))
    np.testing.assert_array_equal(outs[r1], _ref(m, p1, 4))
    assert b.free_page_count == b.n_pages


def test_fused_admission_capacity_divisibility_guard():
    m = _model()
    # cap = ceil(40/8)*8 = 40, C=12 does not divide it
    with pytest.raises(ValueError, match="multiple of prefill_chunk"):
        PagedContinuousBatcher(m, max_batch=2, s_max=40, block_size=8,
                               prefill_chunk=12, fused_admission=True,
                               compile=False)


# -- multi-step decode blocks (decode_block=K) -------------------------------

def test_decode_block_token_exact_vs_single_step():
    """decode_block=K runs K decode steps in ONE executable with
    on-device greedy feedback; tokens must equal the per-step engine's
    exactly — including an EOS finish and a budget (< K) truncation
    mid-block."""
    _retry_load_flake(_decode_block_body, attempts=3)


def _decode_block_body():
    m = _model()
    rng = np.random.RandomState(40)
    prompts = [rng.randint(0, 128, (n,)) for n in (7, 12, 5)]
    budgets = [9, 3, 14]               # 3 < K exercises truncation
    kw = dict(max_batch=4, s_max=32, block_size=8, compile=True)

    ref = PagedContinuousBatcher(m, **kw)
    rids = [ref.submit(p, n) for p, n in zip(prompts, budgets)]
    expected = ref.run_until_done()

    blk = PagedContinuousBatcher(m, decode_block=4, **kw)
    rids2 = [blk.submit(p, n) for p, n in zip(prompts, budgets)]
    outs = blk.run_until_done()
    for r1, r2 in zip(rids, rids2):
        np.testing.assert_array_equal(outs[r2], expected[r1])
    # the block path actually ran (a fallback-only run would also be
    # token-exact, which must not mask a dead feature)
    assert blk.stats()["decode_blocks"] > 0
    assert blk.stats()["generated_tokens"] == sum(budgets)
    assert blk.free_page_count == blk.n_pages


def test_decode_block_eos_mid_block():
    """A request hitting EOS inside a K-block is finished at the EOS
    position; the block's overshoot tokens are discarded."""
    _retry_load_flake(_decode_block_eos_body, attempts=3)


def _decode_block_eos_body():
    m = _model()
    rng = np.random.RandomState(41)
    p = rng.randint(0, 128, (9,))
    ref = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                                 eos_id=None, compile=True)
    r = ref.submit(p, 12)
    full = ref.run_until_done()[r]
    gen = full[len(p):]
    # pick the 3rd generated token as a forced EOS: it lands mid-block
    eos = int(gen[2])
    want = full[:len(p) + 3]

    blk = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                                 eos_id=eos, decode_block=4, compile=True)
    r2 = blk.submit(p, 12)
    out = blk.run_until_done()[r2]
    np.testing.assert_array_equal(out, want)
    assert blk.stats()["decode_blocks"] > 0


def test_decode_block_ondemand_pool_pressure_falls_back():
    """With a pool too small to back a whole K-block, _block_backed
    declines (never preempts) and the per-step path serves the work —
    exactness holds either way."""
    _retry_load_flake(_decode_block_pressure_body, attempts=3)


def _decode_block_pressure_body():
    m = _model()
    rng = np.random.RandomState(42)
    p0 = rng.randint(0, 128, (9,))
    p1 = rng.randint(0, 128, (9,))
    b = PagedContinuousBatcher(m, max_batch=2, s_max=24, block_size=4,
                               n_pages=7, policy="ondemand",
                               decode_block=8, compile=True)
    r0 = b.submit(p0, 8)
    r1 = b.submit(p1, 8)
    outs = b.run_until_done(max_steps=400)
    np.testing.assert_array_equal(outs[r0], _ref(m, p0, 8))
    np.testing.assert_array_equal(outs[r1], _ref(m, p1, 8))
    assert b.free_page_count == b.n_pages


def test_decode_block_guards():
    m = _model()
    with pytest.raises(ValueError, match="decode_block must be >= 2"):
        PagedContinuousBatcher(m, decode_block=1, compile=False)
    with pytest.raises(ValueError, match="greedy"):
        PagedContinuousBatcher(m, decode_block=4, do_sample=True,
                               compile=False)


def test_decode_block_composes_with_fused_admission():
    """fused_admission drains admissions through the fused executable;
    once the queue is empty its idle steps flow through _decode_tail,
    where the K-block takes over. Tokens must match the non-block fused
    engine."""
    _retry_load_flake(_decode_block_fused_body, attempts=3)


def _decode_block_fused_body():
    m = _model()
    rng = np.random.RandomState(43)
    prompts = [rng.randint(0, 128, (n,)) for n in (9, 14)]
    kw = dict(max_batch=2, s_max=32, block_size=8, prefill_chunk=8,
              fused_admission=True, compile=True)
    ref = PagedContinuousBatcher(m, **kw)
    rids = [ref.submit(p, 10) for p in prompts]
    expected = ref.run_until_done()
    blk = PagedContinuousBatcher(m, decode_block=4, **kw)
    rids2 = [blk.submit(p, 10) for p in prompts]
    outs = blk.run_until_done()
    for r1, r2 in zip(rids, rids2):
        np.testing.assert_array_equal(outs[r2], expected[r1])
    assert blk.stats()["decode_blocks"] > 0


def test_decode_block_llama_family():
    """The K-block executable is model-agnostic: the Llama paged decode
    step (GQA + RoPE through the block cache) must be token-exact under
    decode_block too — this is the composition the TPU tier runs on
    hardware (test_tpu_tier.py::test_fused_serving_on_tpu)."""
    _retry_load_flake(_decode_block_llama_body, attempts=3)


def _decode_block_llama_body():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    rng = np.random.RandomState(44)
    prompts = [rng.randint(0, 128, (n,)) for n in (9, 13)]
    kw = dict(max_batch=2, s_max=32, block_size=8, compile=True)
    ref = PagedContinuousBatcher(m, **kw)
    rids = [ref.submit(p, 8) for p in prompts]
    expected = ref.run_until_done()
    blk = PagedContinuousBatcher(m, decode_block=4, **kw)
    rids2 = [blk.submit(p, 8) for p in prompts]
    outs = blk.run_until_done()
    for r1, r2 in zip(rids, rids2):
        np.testing.assert_array_equal(outs[r2], expected[r1])
    assert blk.stats()["decode_blocks"] > 0


# -- the page-granular K/V writer's invariant ------------------------------

def _kv_write_launches(engine="paged"):
    from paddle_tpu.observability.metrics import get_registry
    family = get_registry().get("serving_kv_write_launches_total")
    return {w: family.labels(engine=engine, writer=w).value
            for w in ("page", "row")}


def _watch_written_pages(b):
    """Wrap the batcher's decode launches: before each one, the pages the
    running slots are about to write (one row a step, ``decode_block`` rows
    a block; an unbacked entry is scratch, which nothing reads) must be
    pairwise distinct, in no other slot's table and not the prefix cache's.
    Returns the list the launches are logged to."""
    seen = []

    def check(k_steps):
        live = sorted(b._slot_req)
        cached = set(b.prefix_cache.pages()) if b.prefix_cache else set()
        writes = {}
        for slot in live:
            rows = int(b._dec[slot]) + np.arange(k_steps)
            rows = rows[rows < b.blocks_per_seq * b.block_size]
            pages = set(int(p) for p in b._bt[slot, rows // b.block_size])
            writes[slot] = pages - {b._scratch}
            assert writes[slot], f"slot {slot} writes no backed page"
            assert not writes[slot] & cached, (slot, writes[slot] & cached)
        for slot in live:
            for other in range(b.max_batch):
                if other != slot:
                    held = set(int(p) for p in b._bt[other])
                    assert not writes[slot] & held, (slot, other)
        # parked slots name nothing but scratch
        for slot in set(range(b.max_batch)) - set(live):
            assert set(int(p) for p in b._bt[slot]) == {b._scratch}
        seen.append((k_steps, len(live)))

    def wrap(fn, k_steps):
        def launch(tok, state):
            check(k_steps)
            return fn(tok, state)
        return launch

    b._step_fn = wrap(b._step_fn, 1)
    if b.decode_block:
        b._block_fn = wrap(b._block_fn, b.decode_block)
    return seen


def _row_scatter_route(monkeypatch):
    """Put the Llama family back on the row scatter, decode step and chunk:
    what the page writers' tokens are held against."""
    import jax.numpy as jnp

    from paddle_tpu.incubate.nn.functional import decode_attention as da

    def row_run(pool, table, line, dec, run):
        rows = dec + jnp.arange(run.shape[0])
        block = pool.shape[2]
        pool = pool.at[table[rows // block], :, rows % block].set(
            run.astype(pool.dtype))
        return pool, da._gather_paged(pool, pool, table[None],
                                      pool.shape[1])[0][0]
    monkeypatch.setattr(da, "decode_kv_writer", lambda dtype: "row")
    monkeypatch.setattr(da, "_write_page_run", row_run)


# documents of whole and part pages, each asked several times with another
# question behind it; block_size 4
def _document_sessions(rng, n_docs=2, asks=3):
    docs = [rng.randint(0, 128, (n,)) for n in (16, 22)[:n_docs]]
    return [np.concatenate([docs[i % n_docs], rng.randint(0, 128, (q,))])
            for i, q in enumerate(rng.randint(1, 6, (n_docs * asks,)))]


@pytest.mark.parametrize("options", [
    dict(), dict(policy="ondemand", n_pages=13), dict(decode_block=3),
    dict(prefill_chunk=8)],
    ids=["reserve", "ondemand_preempting", "decode_block", "chunked"])
def test_no_two_sequences_write_one_page(options, monkeypatch):
    """The page writer's invariant, held under the prefix cache: documents
    asked several times share their FULL pages, and at every decode launch
    the pages the running slots write are pairwise distinct, in nobody
    else's table and not the cache's. Tokens equal the row-scatter route's
    and the solo reference's; ``audit_pages()`` is clean; every launch is
    counted under ``writer="page"``."""
    m = _llama()
    prompts = _document_sessions(np.random.RandomState(11))
    budgets = [7, 5, 9, 6, 8, 5]
    kw = dict(max_batch=3, s_max=48, block_size=4, compile=False,
              prefix_cache=True, **options)

    def serve(watch):
        b = PagedContinuousBatcher(m, **kw)
        seen = _watch_written_pages(b) if watch else None
        before = _kv_write_launches()
        rids = [b.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = b.run_until_done()
        assert b.audit_pages() == 0
        s = dict(b.stats(), hit_tokens=b.prefix_cache.hit_tokens)
        counted = {w: n - before[w]
                   for w, n in _kv_write_launches().items()}
        b.close()
        return [outs[r] for r in rids], s, counted, seen

    got, s, counted, seen = serve(watch=True)
    assert s["kv_writer"] == "page" and counted["row"] == 0
    assert counted["page"] == len(seen) > 0
    assert s["hit_tokens"] > 0, "no page was shared"
    assert max(n for _, n in seen) > 1, "no two sequences ever ran together"
    if options.get("decode_block"):
        assert s["decode_blocks"] > 0
        assert counted["page"] == s["steps"] - 2 * s["decode_blocks"]
    else:
        assert counted["page"] == s["steps"]
    if options.get("policy") == "ondemand":
        assert s["preemptions"] > 0, "the pool never ran dry"
    for p, n, out in zip(prompts, budgets, got):
        ids = paddle.to_tensor(np.asarray(p, np.int64)[None, :])
        with paddle.no_grad():
            np.testing.assert_array_equal(
                out, m.generate(ids, max_new_tokens=n).numpy()[0])

    _row_scatter_route(monkeypatch)
    want, s, counted, _ = serve(watch=False)
    assert s["kv_writer"] == "row" and counted["page"] == 0
    assert counted["row"] > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_an_int8_pool_keeps_the_row_scatter():
    """``cache_quant`` allocates int8 pools: rows are quantized on the way
    in by the general op, and the launches are counted under ``row``."""
    m = _llama()
    rng = np.random.RandomState(12)
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False, cache_quant="dynamic_int8")
    assert b.stats()["kv_writer"] == "row"
    before = _kv_write_launches()
    for _ in range(2):
        b.submit(rng.randint(0, 128, (5,)), 6)
    b.run_until_done()
    after = _kv_write_launches()
    assert after["row"] - before["row"] == b.stats()["steps"] > 0
    assert after["page"] == before["page"]


def test_kv_writer_of_a_family_without_the_word_is_row():
    """GPT-2's paged step (``block_multihead_attention``) scatters rows and
    says nothing: the batcher's default."""
    b = PagedContinuousBatcher(_model(), max_batch=2, s_max=32,
                               block_size=8, compile=False)
    assert b.stats()["kv_writer"] == "row"
