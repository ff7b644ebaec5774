"""Chunked prefill in the paged batcher: fixed-width append chunks reproduce
the one-shot prefill token for token, in one executable for every prompt
length, through preemption, and with a tail clamped to the slot's capacity.
(The batcher's scheduling is tests/test_paged_batching.py's.)
"""
import numpy as np

from paddle_tpu.inference.serving import PagedContinuousBatcher

from test_paged_batching import (_llama, _model, _ref, _retry_load_flake)


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
        rids = [b.submit(p, 4) for p in prompts[:2]]
        b.step()
        # two prompts join while the first two decode
        rids += [b.submit(p, 4) for p in prompts[2:]]
        outs = b.run_until_done()
        assert len(b._chunk_fn._cache) == 1, \
            list(b._chunk_fn._cache)      # one signature ever
        assert len(b._step_fn._cache) == 1, list(b._step_fn._cache)
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
    assert b.free_page_count == b.n_pages
    assert b.audit_pages() == 0


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
